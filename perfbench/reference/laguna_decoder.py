"""The plain reference of the Laguna decoder (``model_type: laguna``,
https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json): forward,
loss, gradients and the AdamW update in straightforward ``jax.numpy``,
float32, every product at the highest precision. No kernel, no grouped
product: attention by an explicit mask, each held expert applied densely to
every token and weighted. Blocked over queries only so that 16,384 tokens fit.

Each layer ``l``, with ``x`` the residual stream, ``u = RMSNorm(x)``, positions
restarting at each packed document (the equations of ISSUE 32, written here
from them and not from the program):

- ``h = x + Attn_l(RMSNorm(x))``, ``y = h + MLP_l(RMSNorm(h))``;
- ``Attn_l``: ``H_l = num_attention_heads_per_layer[l]`` query heads on
  ``num_key_value_heads`` key-value heads, no bias. The rotary embedding goes
  by layer type and covers the first ``head_dim * partial_rotary_factor``
  dimensions of a head: frequencies (YaRN's or plain) computed for that many
  dimensions, rotate-half inside them, cos and sin times the attention factor,
  the other dimensions passed unrotated and unscaled, as ``transformers`` does.
  Causal inside a document; on a ``sliding_attention`` layer query ``i`` reads
  ``j`` where ``0 <= i - j < sliding_window``. ``o_h = softmax(q_h k^T /
  sqrt(head_dim)) v``; the gate ``g = sigmoid(u W_g)``, one scalar a head;
  ``Attn_l = W_o concat_h(g_h o_h)``;
- ``MLP_l`` where ``mlp_layer_types[l]`` is ``dense``: ``(SiLU(u W1) * u W3) W2``
  at ``intermediate_size``;
- where it is ``sparse``: ``s = sigmoid(u W_r)`` over all experts, ``E_t`` the
  ``num_experts_per_tok`` largest (equal scores to the lower index), ``w_e =
  moe_routed_scaling_factor * s_e / sum_{j in E_t} s_j``, ``MLP_l(u) =
  Shared(u) + sum_{e in E_t} w_e Expert_e(u)``, the weight on the expert's
  output; none dropped.

The chip's share of a layer as in ``mellum_decoder.py`` (``cfg["share"]``): the
head counts, experts and vocabulary rows in ``cfg`` are the ones held; the
router, the shared expert, a dense layer's MLP and the norms are whole on
every share. ``routed1`` has a row a sparse layer (a dense layer routes
nothing).

Imports nothing from the program; the norm, the rotation of a rotated part,
the quantisers and the optimizer are ``mellum_decoder.py``'s.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from perfbench.reference import mellum_decoder as base
from perfbench.reference.mellum_decoder import (  # noqa: F401 - the interface lm_fit uses
    HIGHEST,
    Params,
    _mm,
    first_gradient,
    head_leaves,
    opt_update,
    quant_bf16,
    quant_e4m3,
    quant_int8,
    rms_norm,
)

# queries a block when the scores are written out: [8, 512, T] float32
QUERY_BLOCK = 512

# what a wrong program would do with the mathematics this family adds
FAULTS = ("no_gate", "softmax_scores", "scale_one", "no_shared", "rotate_whole_head",
          "window_1024", "full_heads_on_window")


def dims(cfg: dict) -> dict:
    z = base.dims(cfg)
    z.update(
        heads=list(cfg["num_attention_heads_per_layer"][: z["layers"]]),
        mlps=list(cfg["mlp_layer_types"][: z["layers"]]),
        f_dense=cfg["intermediate_size"], f_shared=cfg["shared_expert_intermediate_size"],
    )
    return z


layer_types = base.layer_types


def sparse_layers(cfg: dict) -> List[int]:
    return [i for i, kind in enumerate(dims(cfg)["mlps"]) if kind == "sparse"]


def _gated_mlp_spec(prefix: str, d: int, f: int, out: str):
    return {
        f"{prefix}/w_gate/kernel": ((d, f), "dense"),
        f"{prefix}/w_up/kernel": ((d, f), "dense"),
        f"{prefix}/w_down/kernel": ((f, d), out),
    }


def param_spec(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, kind): every trainable leaf, named and shaped as the
    program's tree has it."""
    z = dims(cfg)
    d, hd = z["d"], z["hd"]
    spec = {
        "embed/embedding": ((z["v"], d), "embedding"),
        "final_norm/scale": ((d,), "norm_scale"),
        "head/kernel": ((d, z["v"]), "dense"),
    }
    out = f"residual_out:{2 * z['layers']}"  # into the residual stream: scaled by depth
    for i in range(z["layers"]):
        p, hq = f"layers_{i}", z["heads"][i]
        spec[f"{p}/attn_norm/scale"] = ((d,), "norm_scale")
        spec[f"{p}/attn/wq/kernel"] = ((d, hq * hd), "dense")
        spec[f"{p}/attn/wk/kernel"] = ((d, z["hkv"] * hd), "dense")
        spec[f"{p}/attn/wv/kernel"] = ((d, z["hkv"] * hd), "dense")
        spec[f"{p}/attn/wo/kernel"] = ((hq * hd, d), out)
        spec[f"{p}/attn/head_gate/kernel"] = ((d, hq), "dense")
        if z["mlps"][i] == "dense":
            spec[f"{p}/mlp_norm/scale"] = ((d,), "norm_scale")
            spec.update(_gated_mlp_spec(f"{p}/mlp", d, z["f_dense"], out))
            continue
        spec[f"{p}/moe_norm/scale"] = ((d,), "norm_scale")
        spec[f"{p}/moe/router"] = ((d, z["e_all"]), f"router:{z['n']}")
        spec[f"{p}/moe/w_gate"] = ((z["e"], d, z["f"]), "dense")
        spec[f"{p}/moe/w_up"] = ((z["e"], d, z["f"]), "dense")
        spec[f"{p}/moe/w_down"] = ((z["e"], z["f"], d), out)
        spec.update(_gated_mlp_spec(f"{p}/moe/shared", d, z["f_shared"], out))
    return spec


def router_leaves(cfg: dict) -> List[str]:
    return [f"layers_{i}/moe/router" for i in sparse_layers(cfg)]


def expert_leaves(cfg: dict) -> List[str]:
    return [f"layers_{i}/moe/{w}" for i in sparse_layers(cfg) for w in ("w_gate", "w_up", "w_down")]


def gate_leaves(cfg: dict) -> List[str]:
    return [f"layers_{i}/attn/head_gate/kernel" for i in range(cfg["num_hidden_layers"])]


def shared_leaves(cfg: dict) -> List[str]:
    return sorted(k for k in param_spec(cfg) if "/moe/shared/" in k)


def dense_leaves(cfg: dict) -> List[str]:
    return sorted(k for k in param_spec(cfg) if "/mlp/" in k)


def share_of(full: Params, cfg_full: dict, n: int, s: int) -> Params:
    """Share ``s`` of ``n`` of the uncut model's leaves: of each layer its
    query heads, their gates and the key-value heads they read, its experts,
    its rows of the vocabulary; norms, the router, the shared expert and a
    dense layer's MLP whole."""
    z = dims(cfg_full)
    hd, hkv, e, v = z["hd"], z["hkv"] // n, z["e"] // n, z["v"] // n
    out = {}
    for name, w in full.items():
        layer = name.split("/")[0]
        hq = z["heads"][int(layer.split("_")[1])] // n if layer.startswith("layers_") else 0
        if name.endswith("attn/wq/kernel"):
            w = w[:, s * hq * hd : (s + 1) * hq * hd]
        elif name.endswith("attn/head_gate/kernel"):
            w = w[:, s * hq : (s + 1) * hq]
        elif name.endswith(("attn/wk/kernel", "attn/wv/kernel")):
            w = w[:, s * hkv * hd : (s + 1) * hkv * hd]
        elif name.endswith("attn/wo/kernel"):
            w = w[s * hq * hd : (s + 1) * hq * hd]
        elif name.endswith(("moe/w_gate", "moe/w_up", "moe/w_down")):
            w = w[s * e : (s + 1) * e]
        elif name == "embed/embedding":
            w = w[s * v : (s + 1) * v]
        elif name == "head/kernel":
            w = w[:, s * v : (s + 1) * v]
        out[name] = w
    return out


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------


def rope_parameters(cfg: dict, layer_type: str, *, whole_head: bool = False):
    """(inv_freq [dim / 2], the factor cos and sin are multiplied by, dim):
    ``dim = int(head_dim * partial_rotary_factor)`` leading dimensions of a
    head are rotated, and every constant is computed for that ``dim``, as
    ``transformers`` does (``_compute_yarn_parameters``): ``default`` is
    theta^(-2i/dim); ``yarn`` blends it with the interpolated frequencies by a
    linear ramp between the correction dimensions of beta_fast and beta_slow."""
    rp = cfg["rope_parameters"][layer_type]
    part = 1.0 if whole_head else float(rp.get("partial_rotary_factor", 1.0))
    dim, theta = int(cfg["head_dim"] * part), float(rp["rope_theta"])
    inv = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rp["rope_type"] == "default":
        return jnp.asarray(inv, jnp.float32), 1.0, dim
    factor, orig = float(rp["factor"]), float(rp["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(rp["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rp["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    inv = inv / factor * ramp + inv * (1.0 - ramp)
    scale = rp.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return jnp.asarray(inv, jnp.float32), float(scale), dim


def rotate(x, positions, inv_freq, scale, dim):
    """x [T, H, hd]: the first ``dim`` dimensions rotated, the rest as they are."""
    first = base.apply_rope(x[..., :dim], positions, inv_freq, scale)
    return jnp.concatenate([first, x[..., dim:]], axis=-1)


def attention(cfg, p, prefix, u, segment_ids, positions, i, *, quant, faults, gated=True):
    """One sequence: u [T, d] -> this share's heads' part of Attn_i(u) [T, d].
    ``gated=False`` leaves the gate out (the share test's ungated sum)."""
    z = dims(cfg)
    t, hd, hkv, kind = u.shape[0], z["hd"], z["hkv"], layer_types(cfg)[i]
    hq = z["heads"][i]
    wq, wo, wg = (p[f"{prefix}/{w}/kernel"] for w in ("wq", "wo", "head_gate"))
    if "full_heads_on_window" in faults and kind == "sliding_attention":
        # one head count for every layer: a window layer's further heads left out
        hq = cfg["num_attention_heads"]
        wq, wo, wg = wq[:, : hq * hd], wo[: hq * hd], wg[:, :hq]
    q = _mm(u, wq, quant).reshape(t, hq, hd)
    k = _mm(u, p[f"{prefix}/wk/kernel"], quant).reshape(t, hkv, hd)
    v = _mm(u, p[f"{prefix}/wv/kernel"], quant).reshape(t, hkv, hd)
    rope = rope_parameters(cfg, kind, whole_head="rotate_whole_head" in faults)
    q, k = rotate(q, positions, *rope), rotate(k, positions, *rope)
    k = jnp.repeat(k, hq // hkv, axis=1)  # query head j reads key-value head j // group
    v = jnp.repeat(v, hq // hkv, axis=1)
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    if window is not None and "window_1024" in faults:
        window = 1024
    idx = jnp.arange(t)
    block = math.gcd(t, QUERY_BLOCK)
    cast = quant if quant else (lambda x: x)

    @jax.checkpoint
    def rows(start):
        qi = lax.dynamic_slice_in_dim(q, start, block, 0)
        pos = start + jnp.arange(block)
        scores = jnp.einsum("qhd,khd->hqk", cast(qi), cast(k), precision=HIGHEST) / math.sqrt(hd)
        seen = (idx[None, :] <= pos[:, None]) & (
            lax.dynamic_slice_in_dim(segment_ids, start, block, 0)[:, None] == segment_ids[None, :])
        if window is not None:
            seen &= pos[:, None] - idx[None, :] < window
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", cast(probs), cast(v), precision=HIGHEST)

    out = lax.map(rows, jnp.arange(0, t, block)).reshape(t, hq, hd)
    if gated and "no_gate" not in faults:
        # the gate's product in float32 at the highest precision, like the router's
        out = out * jax.nn.sigmoid(_mm(u, wg, None))[:, :, None]
    return _mm(out.reshape(t, hq * hd), wo, quant)


def gated_mlp(p, prefix, u, *, quant):
    hidden = jax.nn.silu(_mm(u, p[f"{prefix}/w_gate/kernel"], quant)) \
        * _mm(u, p[f"{prefix}/w_up/kernel"], quant)
    return _mm(hidden, p[f"{prefix}/w_down/kernel"], quant)


def route(cfg, logits, *, faults=()):
    """Router logits [T, E_all] -> (weights [T, E_all], zero off the chosen
    experts, and the chosen mask): each logit's sigmoid, the k largest (equal
    scores to the lower index), renormalised over the chosen, times the routed
    scaling factor."""
    logits = logits.astype(jnp.float32)
    scores = jax.nn.softmax(logits, axis=-1) if "softmax_scores" in faults \
        else jax.nn.sigmoid(logits)
    k = cfg["num_experts_per_tok"] - (1 if "top_k_minus_one" in faults else 0)
    top_s, top_e = lax.top_k(scores, k)
    if "no_renorm" not in faults:
        top_s = top_s / jnp.sum(top_s, axis=-1, keepdims=True)
    if "scale_one" not in faults:
        top_s = top_s * float(cfg["moe_routed_scaling_factor"])
    one_hot = jax.nn.one_hot(top_e, scores.shape[-1], dtype=jnp.float32)
    return jnp.sum(one_hot * top_s[..., None], axis=1), jnp.sum(one_hot, axis=1)


def routed(cfg, p, prefix, u, *, quant, faults):
    """u [T, d] -> (this share's routed experts' part of MLP(u), tokens routed
    to each held expert [E])."""
    z = dims(cfg)
    weights, chosen = route(cfg, _mm(u, p[f"{prefix}/router"], None), faults=faults)
    mine = slice(z["s"] * z["e"], (z["s"] + 1) * z["e"])
    weights, chosen = weights[:, mine], chosen[:, mine]
    counts = jnp.sum(chosen, axis=0)
    if "capacity" in faults:
        # the fault a capacity buffer has: an expert's tokens beyond
        # 1.25 x the even share are dropped, in arrival order
        capacity = int(1.25 * u.shape[0] * z["k"] / z["e_all"])
        weights = jnp.where(jnp.cumsum(chosen, axis=0) <= capacity, weights, 0.0)

    @jax.checkpoint
    def one(carry, xs):
        w_gate, w_up, w_down, w_e = xs
        hidden = jax.nn.silu(_mm(u, w_gate, quant)) * _mm(u, w_up, quant)
        return carry + w_e[:, None] * _mm(hidden, w_down, quant), None

    out, _ = lax.scan(one, jnp.zeros_like(u), (
        p[f"{prefix}/w_gate"], p[f"{prefix}/w_up"], p[f"{prefix}/w_down"], weights.T))
    return out, counts


def layer(cfg, p, i, x, segment_ids, positions, *, quant=None, faults=()):
    """Layer ``i`` on one sequence: (y [T, d], routed counts [E] or None)."""
    eps, prefix = cfg["rms_norm_eps"], f"layers_{i}"
    h = x + attention(cfg, p, f"{prefix}/attn", rms_norm(x, p[f"{prefix}/attn_norm/scale"], eps),
                      segment_ids, positions, i, quant=quant, faults=faults)
    if dims(cfg)["mlps"][i] == "dense":
        u = rms_norm(h, p[f"{prefix}/mlp_norm/scale"], eps)
        return h + gated_mlp(p, f"{prefix}/mlp", u, quant=quant), None
    u = rms_norm(h, p[f"{prefix}/moe_norm/scale"], eps)
    out, counts = routed(cfg, p, f"{prefix}/moe", u, quant=quant, faults=faults)
    if "no_shared" not in faults:
        out = out + gated_mlp(p, f"{prefix}/moe/shared", u, quant=quant)
    return h + out, counts


# -- a layer's parts alone, for the share test ---------------------------------


def attention_part(cfg, p, i, u, segment_ids, positions):
    return attention(cfg, p, f"layers_{i}/attn", u, segment_ids, positions, i,
                     quant=None, faults=())


def routed_part(cfg, p, i, u):
    return routed(cfg, p, f"layers_{i}/moe", u, quant=None, faults=())[0]


def shared_part(cfg, p, i, u):
    return gated_mlp(p, f"layers_{i}/moe/shared", u, quant=None)


def dense_part(cfg, p, i, u):
    return gated_mlp(p, f"layers_{i}/mlp", u, quant=None)


# ---------------------------------------------------------------------------
# loss, gradients, steps
# ---------------------------------------------------------------------------


def sequence_loss(cfg, p, seq, *, quant=None, faults=()):
    """(summed cross-entropy over the positions that have a next token in
    their document, (how many there are, routed counts [sparse layers, E]))."""
    x = p["embed/embedding"][seq["tokens"]]
    counts = []
    for i in range(cfg["num_hidden_layers"]):
        x, c = jax.checkpoint(
            lambda x, p, i=i: layer(cfg, p, i, x, seq["segment_ids"], seq["positions"],
                                    quant=quant, faults=faults)
        )(x, p)
        if c is not None:
            counts.append(c)
    x = rms_norm(x, p["final_norm/scale"], cfg["rms_norm_eps"])
    logits = _mm(x, p["head/kernel"], quant)
    has = seq["targets"] >= 0
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, jnp.maximum(seq["targets"], 0)[:, None], axis=-1)[:, 0]
    return -jnp.sum(jnp.where(has, picked, 0.0)), (jnp.sum(has), jnp.stack(counts))


def batch_loss_and_grad(cfg, p, batch, *, quant=None, faults=()):
    """Mean cross-entropy of a batch [B, T] of packed sequences, its gradient,
    and the routed counts summed over the sequences: one sequence at a time."""
    if "drop_half" in faults:
        batch = {k: v[: v.shape[0] // 2] for k, v in batch.items()}

    def part(seq):
        return jax.value_and_grad(
            lambda q: sequence_loss(cfg, q, seq, quant=quant, faults=faults), has_aux=True
        )(p)

    first = {k: v[0] for k, v in batch.items()}
    zero = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(part, first))
    total, _ = lax.scan(
        lambda acc, seq: (jax.tree.map(jnp.add, acc, part(seq)), None), zero, batch
    )
    (loss_sum, (n_targets, counts)), grads = total
    n = jnp.maximum(n_targets, 1).astype(jnp.float32)
    return loss_sum / n, jax.tree.map(lambda g: g / n, grads), counts


def make_step(cfg: dict, *, quant: Optional[Callable] = None, faults: Sequence[str] = ()):
    """One jitted update ``(params, opt, batch, count) -> (params, opt, loss,
    routed counts)``; the state is donated."""
    faults = tuple(faults)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(p, opt, batch, count):
        loss, grads, counts = batch_loss_and_grad(cfg, p, batch, quant=quant, faults=faults)
        new_p, new_opt = opt_update(cfg, grads, opt, p, count)
        if "unchanged" in faults:
            new_p = jax.tree.map(jnp.copy, p)
        return new_p, new_opt, loss, counts

    return step


def train_steps(
    cfg: dict,
    params: Params,
    batches: Sequence[dict],
    *,
    quant: Optional[Callable] = None,
    faults: Sequence[str] = (),
):
    """Follow ``len(batches)`` updates from ``params`` (which the first step
    consumes: pass a copy): the per-step losses, the first gradient, the first
    step's routed counts [sparse layers, E] and the parameters after the last
    step. ``faults``: ``FAULTS`` here (the gate left out, a softmax's scores,
    the scale left at 1, no shared expert, the whole head rotated, a window of
    1,024, a window layer cut to the full layers' head count) and
    ``top_k_minus_one``, ``no_renorm``, ``capacity``, ``drop_half``,
    ``unchanged`` as ``mellum_decoder.train_steps`` has them."""
    step = make_step(cfg, quant=quant, faults=faults)
    zeros = jax.jit(lambda p: {k: jnp.zeros_like(v) for k, v in p.items()})
    params = jax.tree.map(jnp.asarray, dict(params))
    opt = {"mu": zeros(params), "nu": zeros(params)}
    losses, grad1, routed1 = [], None, None
    for count, batch in enumerate(batches):
        batch = {k: jnp.asarray(batch[k])
                 for k in ("tokens", "segment_ids", "positions", "targets")}
        params, opt, loss, counts = step(params, opt, batch, jnp.asarray(count, jnp.int32))
        losses.append(loss)
        if count == 0:
            # to the host at once: a fourth tree does not fit on the chip
            grad1 = first_gradient(cfg, {"mu": jax.device_get(opt["mu"])})
            routed1 = jax.device_get(counts)
    return {"losses": [float(x) for x in losses], "grad1": grad1, "routed1": routed1,
            "params": jax.device_get(params)}
