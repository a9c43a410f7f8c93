"""The plain reference of the Kimi-Linear decoder (``model_type: kimi_linear``,
https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json):
forward, loss, gradients and the AdamW update in straightforward
``jax.numpy``, float32, every product at the highest precision. No kernel, no
chunked form, no grouped product: the delta rule one position at a time
(checkpointed every ``SCAN_SEGMENT`` positions so that its gradient fits),
attention by an explicit mask, each held expert applied densely to every
token and weighted. Blocked over queries and over the head's tokens only so
that 16,384 tokens fit.

Each layer ``l``, with ``x`` the residual stream and positions restarting at
each packed document (the equations of ISSUE 36, written here from them and
not from the program): ``h = x + Mix_l(RMSNorm x)``, ``y = h + MLP_l(RMSNorm
h)``, ``rms_norm_eps`` 1e-5. Layer ``i`` is KDA where ``i + 1`` is in
``linear_attn_config.kda_layers``, else MLA.

- **KDA**, per head of ``d = 128``: ``q, k = L2norm(SiLU(conv4(u W_q|k)))``,
  ``v = SiLU(conv4(u W_v))`` (causal, depthwise, no bias, inside the
  document); ``g = -exp(A_log_h) softplus(u W_fa W_fb + dt_bias)`` by
  channel; ``beta = sigmoid(u W_b)``; ``S_t = (I - beta_t k_t k_t^T)
  Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T``, ``S`` 0 at each document's
  start; ``o_t = d^-1/2 S_t^T q_t``; ``Mix = W_o concat_h(RMSNorm_d(o_h) *
  sigmoid(u W_ga W_gb)_h)``;
- **MLA** (NoPE): ``q_h = u W_q = [q_nope 128, q_pe 64]``; ``c = u W_kva``
  (576); ``[k_nope_h, v_h] = RMSNorm_512(c[:512]) W_kvb``, ``k_pe = c[512:]``
  every head's and not rotated; causal softmax inside the document over
  ``q_h . [k_nope_h, k_pe] / sqrt(192)``; ``Mix = W_o concat_h``;
- **MLP**: layer 0 a SiLU-gated MLP of 9,216; then ``s = sigmoid(u W_r)``
  over 256 experts, the 8 largest (ties to the lower index), ``w_e = 2.446
  s_e / sum s``, ``MLP(u) = Shared(u) + sum w_e Expert_e(u)``, every expert
  SiLU-gated of 1,024, none dropped; the selection-only correction bias is
  left out, as in the program.

The chip's share (``cfg["share"]``): ``num_experts`` and ``vocab_size`` in
``cfg`` are the ones held; attention (KDA and MLA), layer 0's MLP, the shared
expert, the router and the norms are whole on every share. ``routed1`` has a
row a sparse layer.

Imports nothing from the program; the norm, the quantisers and the optimizer
are ``mellum_decoder.py``'s.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

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

# positions a checkpointed stretch of the delta rule's scan
SCAN_SEGMENT = 256
# queries a block when MLA's scores are written out: [32, 128, T] float32
QUERY_BLOCK = 128
# tokens a block of the head's loss: [4096, V] float32 logits at once
LOSS_BLOCK = 4096
L2_EPS = 1e-6

# what a wrong program would do with the mathematics this family adds: the
# delta correction dropped (plain gated linear attention), one decay a head
# (the channels' mean) in place of one a channel, a rotary embedding on the
# shared key part and the query's, and the state carried over a document start
FAULTS = ("no_delta", "decay_by_head", "rotate_pe", "no_reset")


def dims(cfg: dict) -> dict:
    n, s = int(cfg["share"]["n"]), int(cfg["share"]["s"])
    lin = cfg["linear_attn_config"]
    layers = cfg["num_hidden_layers"]
    first = cfg["first_k_dense_replace"]
    return dict(
        d=cfg["hidden_size"], h=cfg["num_attention_heads"], lh=lin["num_heads"],
        ld=lin["head_dim"], conv=lin["short_conv_kernel_size"],
        rank=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        vd=cfg["v_head_dim"], e=cfg["num_experts"], e_all=cfg["num_experts"] * n,
        k=cfg["num_experts_per_token"], f=cfg["moe_intermediate_size"],
        f_dense=cfg["intermediate_size"],
        f_shared=cfg["moe_intermediate_size"] * cfg["num_shared_experts"],
        v=cfg["vocab_size"], layers=layers, n=n, s=s,
        kinds=["linear" if i + 1 in lin["kda_layers"] else "latent" for i in range(layers)],
        mlps=["sparse" if i >= first and i % cfg["moe_layer_freq"] == 0 else "dense"
              for i in range(layers)],
    )


def sparse_layers(cfg: dict) -> List[int]:
    return [i for i, kind in enumerate(dims(cfg)["mlps"]) if kind == "sparse"]


def _gated_mlp_spec(prefix: str, d: int, f: int, out: str):
    return {
        f"{prefix}/w_gate/kernel": ((d, f), "dense"),
        f"{prefix}/w_up/kernel": ((d, f), "dense"),
        f"{prefix}/w_down/kernel": ((f, d), out),
    }


def _linear_spec(p: str, z: dict, out: str):
    d, hd = z["d"], z["lh"] * z["ld"]
    spec = {f"{p}/{w}_proj/kernel": ((d, hd), "dense") for w in "qkv"}
    spec.update({f"{p}/{w}_conv1d/kernel": ((z["conv"], hd), "dense") for w in "qkv"})
    spec.update({
        f"{p}/f_a_proj/kernel": ((d, z["ld"]), "dense"),
        f"{p}/f_b_proj/kernel": ((z["ld"], hd), "dense"),
        f"{p}/b_proj/kernel": ((d, z["lh"]), "dense"),
        f"{p}/g_a_proj/kernel": ((d, z["ld"]), "dense"),
        f"{p}/g_b_proj/kernel": ((z["ld"], hd), "dense"),
        f"{p}/A_log": ((z["lh"],), "a_log"),
        f"{p}/dt_bias": ((hd,), "dt_bias"),
        f"{p}/o_norm/scale": ((z["ld"],), "norm_scale"),
        f"{p}/o_proj/kernel": ((hd, d), out),
    })
    return spec


def _latent_spec(p: str, z: dict, out: str):
    d, h = z["d"], z["h"]
    return {
        f"{p}/q_proj/kernel": ((d, h * (z["nope"] + z["rope"])), "dense"),
        f"{p}/kv_a_proj_with_mqa/kernel": ((d, z["rank"] + z["rope"]), "dense"),
        f"{p}/kv_a_layernorm/scale": ((z["rank"],), "norm_scale"),
        f"{p}/kv_b_proj/kernel": ((z["rank"], h * (z["nope"] + z["vd"])), "dense"),
        f"{p}/o_proj/kernel": ((h * z["vd"], d), out),
    }


def param_spec(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, kind): every trainable leaf, named and shaped as the
    program's tree has it. ``a_log`` and ``dt_bias`` are the family's
    initialisation (``perfbench/lm_linear_weights.py``)."""
    z = dims(cfg)
    d = z["d"]
    spec = {
        "embed/embedding": ((z["v"], d), "embedding"),
        "final_norm/scale": ((d,), "norm_scale"),
        "head/kernel": ((d, z["v"]), "dense"),
    }
    out = f"residual_out:{2 * z['layers']}"  # into the residual stream: scaled by depth
    for i in range(z["layers"]):
        p = f"layers_{i}"
        spec[f"{p}/attn_norm/scale"] = ((d,), "norm_scale")
        mixer = _linear_spec if z["kinds"][i] == "linear" else _latent_spec
        spec.update(mixer(f"{p}/attn", z, out))
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


def _mixer_leaves(cfg: dict, kind: str) -> List[str]:
    z = dims(cfg)
    return sorted(k for k in param_spec(cfg) if "/attn/" in k
                  and z["kinds"][int(k.split("/")[0].split("_")[1])] == kind)


def gate_leaves(cfg: dict) -> List[str]:
    """The KDA layers' output gates."""
    return [k for k in _mixer_leaves(cfg, "linear") if "/g_a_proj/" in k or "/g_b_proj/" in k]


def kda_leaves(cfg: dict) -> List[str]:
    """The KDA layers' other leaves: projections, convolutions, decay, beta,
    the output norm."""
    return [k for k in _mixer_leaves(cfg, "linear") if k not in gate_leaves(cfg)]


def latent_leaves(cfg: dict) -> List[str]:
    return _mixer_leaves(cfg, "latent")


def shared_leaves(cfg: dict) -> List[str]:
    return sorted(k for k in param_spec(cfg) if "/moe/shared/" in k)


def dense_leaves(cfg: dict) -> List[str]:
    return sorted(k for k in param_spec(cfg) if "/mlp/" in k)


def share_of(full: Params, cfg_full: dict, n: int, s: int) -> Params:
    """Share ``s`` of ``n`` of the uncut model's leaves: of each layer its
    experts and of the vocabulary its rows; attention, norms, the router,
    the shared expert and the dense layer whole."""
    z = dims(cfg_full)
    e, v = z["e"] // n, z["v"] // n
    out = {}
    for name, w in full.items():
        if name.endswith(("moe/w_gate", "moe/w_up", "moe/w_down")):
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


def short_conv(x, w, positions):
    """x [T, C], w [K, C]: y_t = sum_j w[K - 1 - j] x_{t - j} over j <= positions_t."""
    out = x * w[-1]
    for j in range(1, w.shape[0]):
        earlier = jnp.concatenate([jnp.zeros((j, x.shape[1]), x.dtype), x[:-j]], axis=0)
        out = out + jnp.where((positions >= j)[:, None], earlier, 0.0) * w[-1 - j]
    return out


def l2_normalize(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


def delta_rule(q, k, v, g, beta, positions, *, faults=()):
    """q, k, g [T, H, d], v [T, H, d], beta [T, H] -> o [T, H, d]: the
    recurrence one position at a time (q already scaled)."""
    t, h, dk = k.shape
    delta = "no_delta" not in faults
    reset = "no_reset" not in faults

    def step(state, xs):
        qt, kt, vt, gt, bt, pt = xs
        if reset:
            state = jnp.where(pt == 0, 0.0, state)
        state = state * jnp.exp(gt)[:, :, None]
        if delta:
            state = state - bt[:, None, None] * kt[:, :, None] * jnp.einsum(
                "hk,hkv->hv", kt, state, precision=HIGHEST)[:, None, :]
        state = state + bt[:, None, None] * kt[:, :, None] * vt[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, qt, precision=HIGHEST)

    @jax.checkpoint
    def stretch(state, xs):
        return lax.scan(step, state, xs)

    seg = math.gcd(t, SCAN_SEGMENT)
    xs = tuple(x.reshape(t // seg, seg, *x.shape[1:]) for x in (q, k, v, g, beta, positions))
    _, o = lax.scan(stretch, jnp.zeros((h, dk, v.shape[-1]), jnp.float32), xs)
    return o.reshape(t, h, v.shape[-1])


def linear_attention(cfg, p, prefix, u, positions, *, quant, faults):
    """One sequence: u [T, d] -> a KDA layer's Mix(u) [T, d]."""
    z = dims(cfg)
    t, h, dk = u.shape[0], z["lh"], z["ld"]
    proj = lambda name, x: _mm(x, p[f"{prefix}/{name}/kernel"], quant)  # noqa: E731
    exact = lambda name, x: _mm(x, p[f"{prefix}/{name}/kernel"], None)  # noqa: E731

    def branch(w):
        x = short_conv(proj(f"{w}_proj", u), p[f"{prefix}/{w}_conv1d/kernel"], positions)
        return jax.nn.silu(x).reshape(t, h, dk)

    q, k, v = branch("q"), branch("k"), branch("v")
    q, k = l2_normalize(q) * dk**-0.5, l2_normalize(k)
    f = exact("f_b_proj", exact("f_a_proj", u)) + p[f"{prefix}/dt_bias"]
    g = -jnp.exp(p[f"{prefix}/A_log"])[:, None] * jax.nn.softplus(f.reshape(t, h, dk))
    if "decay_by_head" in faults:
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(exact("b_proj", u))
    cast = quant if quant else (lambda x: x)
    o = delta_rule(cast(q), cast(k), cast(v), g, beta, positions, faults=faults)
    gate = jax.nn.sigmoid(exact("g_b_proj", exact("g_a_proj", u))).reshape(t, h, dk)
    o = rms_norm(o, p[f"{prefix}/o_norm/scale"], cfg["rms_norm_eps"]) * gate
    return _mm(o.reshape(t, h * dk), p[f"{prefix}/o_proj/kernel"], quant)


def rotate(x, positions, theta):
    """Rotate-half over the last axis of x [T, ..., r] (the ``rotate_pe`` fault)."""
    r = x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angles = positions.astype(jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([angles, angles], axis=-1).reshape(
        (x.shape[0],) + (1,) * (x.ndim - 2) + (r,))
    rotated = jnp.concatenate([-x[..., r // 2:], x[..., : r // 2]], axis=-1)
    return x * jnp.cos(emb) + rotated * jnp.sin(emb)


def latent_attention(cfg, p, prefix, u, segment_ids, positions, *, quant, faults):
    """One sequence: u [T, d] -> an MLA layer's Mix(u) [T, d]."""
    z = dims(cfg)
    t, h, nope, rope, vd = u.shape[0], z["h"], z["nope"], z["rope"], z["vd"]
    q = _mm(u, p[f"{prefix}/q_proj/kernel"], quant).reshape(t, h, nope + rope)
    c = _mm(u, p[f"{prefix}/kv_a_proj_with_mqa/kernel"], quant)
    kv = _mm(rms_norm(c[:, : z["rank"]], p[f"{prefix}/kv_a_layernorm/scale"], cfg["rms_norm_eps"]),
             p[f"{prefix}/kv_b_proj/kernel"], quant).reshape(t, h, nope + vd)
    k_pe = c[:, z["rank"]:]
    if "rotate_pe" in faults:
        q_pe = rotate(q[..., nope:], positions, cfg["rope_theta"])
        q = jnp.concatenate([q[..., :nope], q_pe], -1)
        k_pe = rotate(k_pe, positions, cfg["rope_theta"])
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe[:, None, :], (t, h, rope))], -1)
    v = kv[..., nope:]
    idx = jnp.arange(t)
    block = math.gcd(t, QUERY_BLOCK)
    cast = quant if quant else (lambda x: x)

    @jax.checkpoint
    def rows(start):
        qi = lax.dynamic_slice_in_dim(q, start, block, 0)
        i = start + jnp.arange(block)
        scores = jnp.einsum("qhd,khd->hqk", cast(qi), cast(k), precision=HIGHEST) \
            / math.sqrt(nope + rope)
        seen = (idx[None, :] <= i[:, None]) & (
            lax.dynamic_slice_in_dim(segment_ids, start, block, 0)[:, None] == segment_ids[None, :])
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", cast(probs), cast(v), precision=HIGHEST)

    out = lax.map(rows, jnp.arange(0, t, block)).reshape(t, h * vd)
    return _mm(out, p[f"{prefix}/o_proj/kernel"], quant)


def gated_mlp(p, prefix, u, *, quant):
    hidden = jax.nn.silu(_mm(u, p[f"{prefix}/w_gate/kernel"], quant)) \
        * _mm(u, p[f"{prefix}/w_up/kernel"], quant)
    return _mm(hidden, p[f"{prefix}/w_down/kernel"], quant)


def route(cfg, logits, *, faults=()):
    """Router logits [T, E_all] -> (weights [T, E_all], zero off the chosen
    experts, and the chosen mask): each logit's sigmoid, the k largest by
    ``lax.top_k`` as in the program (of equal scores the lower index on the CPU,
    not always on a TPU), renormalised, times the routed scaling factor."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    k = cfg["num_experts_per_token"] - (1 if "top_k_minus_one" in faults else 0)
    top_s, top_e = lax.top_k(scores, k)
    top_s = top_s / jnp.sum(top_s, axis=-1, keepdims=True) * float(cfg["routed_scaling_factor"])
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


def mixer(cfg, p, i, u, segment_ids, positions, *, quant=None, faults=()):
    prefix = f"layers_{i}/attn"
    if dims(cfg)["kinds"][i] == "linear":
        return linear_attention(cfg, p, prefix, u, positions, quant=quant, faults=faults)
    return latent_attention(cfg, p, prefix, u, segment_ids, positions, quant=quant, faults=faults)


def layer(cfg, p, i, x, segment_ids, positions, *, quant=None, faults=()):
    """Layer ``i`` on one sequence: (y [T, d], routed counts [E] or None)."""
    eps, prefix = cfg["rms_norm_eps"], f"layers_{i}"
    h = x + mixer(cfg, p, i, rms_norm(x, p[f"{prefix}/attn_norm/scale"], eps), segment_ids,
                  positions, quant=quant, faults=faults)
    if dims(cfg)["mlps"][i] == "dense":
        u = rms_norm(h, p[f"{prefix}/mlp_norm/scale"], eps)
        return h + gated_mlp(p, f"{prefix}/mlp", u, quant=quant), None
    u = rms_norm(h, p[f"{prefix}/moe_norm/scale"], eps)
    out, counts = routed(cfg, p, f"{prefix}/moe", u, quant=quant, faults=faults)
    return h + out + gated_mlp(p, f"{prefix}/moe/shared", u, quant=quant), counts


# -- a layer's parts alone, for the share test ---------------------------------


def routed_part(cfg, p, i, u):
    return routed(cfg, p, f"layers_{i}/moe", u, quant=None, faults=())[0]


def shared_part(cfg, p, i, u):
    return gated_mlp(p, f"layers_{i}/moe/shared", u, quant=None)


# ---------------------------------------------------------------------------
# loss, gradients, steps
# ---------------------------------------------------------------------------


def sequence_loss(cfg, p, seq, *, quant=None, faults=()):
    """(summed cross-entropy over the positions that have a next token in
    their document, (how many there are, routed counts [sparse layers, E])):
    the head's logits ``LOSS_BLOCK`` tokens at a time."""
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
    block = math.gcd(x.shape[0], LOSS_BLOCK)

    @jax.checkpoint
    def part(total, xs):
        xb, tb = xs
        logp = jax.nn.log_softmax(_mm(xb, p["head/kernel"], quant), axis=-1)
        picked = jnp.take_along_axis(logp, jnp.maximum(tb, 0)[:, None], axis=-1)[:, 0]
        return total - jnp.sum(jnp.where(tb >= 0, picked, 0.0)), None

    loss, _ = lax.scan(part, jnp.zeros((), jnp.float32), (
        x.reshape(-1, block, x.shape[-1]), seq["targets"].reshape(-1, block)))
    return loss, (jnp.sum(seq["targets"] >= 0), jnp.stack(counts))


def batch_loss_and_grad(cfg, p, batch, *, quant=None, faults=()):
    """Mean cross-entropy of a batch [B, T] of packed sequences, its gradient,
    and the routed counts summed over the sequences: one sequence at a time."""
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
    step. ``faults``: ``FAULTS`` here and ``top_k_minus_one``, ``capacity``,
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
