"""The plain reference of the Mellum-2 decoder (``model_type: mellum``,
https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json):
forward, loss, gradients and the AdamW update in straightforward
``jax.numpy``, float32, every product at the highest precision. No kernel, no
grouped product: attention by an explicit mask built from the segment ids,
each held expert applied densely to every token and weighted. Blocked over
queries and over sequences only so that it fits the chip at the timed size.

It takes the chip's share of a layer the way the program does: ``cfg`` is the
configuration file, whose head, expert and vocabulary counts are the ones held
here, with ``cfg["share"] = {"n": chips that share a layer, "s": which of
them}``; ``n = 1`` is the uncut model. The router keeps ``num_experts * n``
outputs, its top-k and its renormalisation over all chosen experts, held or
not; what the absent heads and experts would have added is left out.

Imports nothing from the program.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Params = Dict[str, jax.Array]
HIGHEST = lax.Precision.HIGHEST

# AdamW as optax's defaults have it (the configuration file's `train` holds
# the rate and the decay)
B1, B2, EPS = 0.9, 0.999, 1e-8
# queries a block when the scores are written out: [heads, 1024, T] float32
QUERY_BLOCK = 1024


def dims(cfg: dict) -> dict:
    n, s = int(cfg["share"]["n"]), int(cfg["share"]["s"])
    return dict(
        d=cfg["hidden_size"], hd=cfg["head_dim"], hq=cfg["num_attention_heads"],
        hkv=cfg["num_key_value_heads"], e=cfg["num_experts"], e_all=cfg["num_experts"] * n,
        k=cfg["num_experts_per_tok"], f=cfg["moe_intermediate_size"], v=cfg["vocab_size"],
        layers=cfg["num_hidden_layers"], n=n, s=s,
    )


def layer_types(cfg: dict) -> List[str]:
    return list(cfg["layer_types"][: cfg["num_hidden_layers"]])


def param_spec(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, kind): every trainable leaf, named and shaped as the
    program's tree has it."""
    z = dims(cfg)
    spec = {
        "embed/embedding": ((z["v"], z["d"]), "embedding"),
        "final_norm/scale": ((z["d"],), "norm_scale"),
        "head/kernel": ((z["d"], z["v"]), "dense"),
    }
    out = f"residual_out:{2 * z['layers']}"  # into the residual stream: scaled by depth
    for i in range(z["layers"]):
        p = f"layers_{i}"
        spec[f"{p}/attn_norm/scale"] = ((z["d"],), "norm_scale")
        spec[f"{p}/attn/wq/kernel"] = ((z["d"], z["hq"] * z["hd"]), "dense")
        spec[f"{p}/attn/wk/kernel"] = ((z["d"], z["hkv"] * z["hd"]), "dense")
        spec[f"{p}/attn/wv/kernel"] = ((z["d"], z["hkv"] * z["hd"]), "dense")
        spec[f"{p}/attn/wo/kernel"] = ((z["hq"] * z["hd"], z["d"]), out)
        spec[f"{p}/moe_norm/scale"] = ((z["d"],), "norm_scale")
        spec[f"{p}/moe/router"] = ((z["d"], z["e_all"]), f"router:{z['n']}")
        spec[f"{p}/moe/w_gate"] = ((z["e"], z["d"], z["f"]), "dense")
        spec[f"{p}/moe/w_up"] = ((z["e"], z["d"], z["f"]), "dense")
        spec[f"{p}/moe/w_down"] = ((z["e"], z["f"], z["d"]), out)
    return spec


def head_leaves(cfg: dict) -> List[str]:
    return ["head/kernel"]


def router_leaves(cfg: dict) -> List[str]:
    return [f"layers_{i}/moe/router" for i in range(cfg["num_hidden_layers"])]


def expert_leaves(cfg: dict) -> List[str]:
    return [f"layers_{i}/moe/{w}" for i in range(cfg["num_hidden_layers"])
            for w in ("w_gate", "w_up", "w_down")]


def share_of(full: Params, cfg_full: dict, n: int, s: int) -> Params:
    """Share ``s`` of ``n`` of the uncut model's leaves: its query heads with
    their key-value heads, its experts, its rows of the vocabulary; norms and
    the router whole."""
    z = dims(cfg_full)
    hd, hq, hkv, e, v = z["hd"], z["hq"] // n, z["hkv"] // n, z["e"] // n, z["v"] // n
    out = {}
    for name, w in full.items():
        if name.endswith("attn/wq/kernel"):
            w = w[:, s * hq * hd : (s + 1) * hq * hd]
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
# the control's precision: below the bfloat16 the configuration states
# ---------------------------------------------------------------------------


def quant_int8(x):
    """Per-tensor symmetric int8 and back, straight through for gradients."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + lax.stop_gradient(q - x)


def quant_e4m3(x):
    """Per-tensor scaled fp8 (e4m3) and back, straight through for gradients."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(jnp.float8_e4m3fn).max)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(q - x)


def quant_bf16(x):
    return x + lax.stop_gradient(x.astype(jnp.bfloat16).astype(jnp.float32) - x)


def _mm(a, b, quant):
    """Every matrix product of the model: float32 at the highest precision,
    its operands through ``quant`` first where a control asks."""
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.matmul(a, b, precision=HIGHEST)


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------


def rms_norm(x, scale, eps):
    return scale * x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def rope_parameters(cfg: dict, layer_type: str, *, yarn: bool = True):
    """(inv_freq [head_dim / 2], the factor cos and sin are multiplied by), as
    ``transformers`` computes them: ``default`` is theta^(-2i/d); ``yarn``
    blends it with the interpolated frequencies by a linear ramp between the
    correction dimensions of beta_fast and beta_slow."""
    rp = cfg["rope_parameters"][layer_type]
    dim, theta = cfg["head_dim"], float(rp["rope_theta"])
    # constants: in float64, then what float32 holds of them
    inv = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rp["rope_type"] == "default" or not yarn:
        return jnp.asarray(inv, jnp.float32), 1.0
    factor, orig = float(rp["factor"]), float(rp["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(rp["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rp["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp  # the share of the uninterpolated frequency
    inv = inv / factor * (1.0 - keep) + inv * keep
    scale = rp.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return jnp.asarray(inv, jnp.float32), float(scale)


def apply_rope(x, positions, inv_freq, scale):
    """x [T, H, hd], positions [T]: rotate-half, as ``transformers`` lays it out."""
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = (jnp.cos(emb) * scale)[:, None, :], (jnp.sin(emb) * scale)[:, None, :]
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def attention(cfg, p, prefix, u, segment_ids, positions, layer_type, *, quant, faults):
    """One sequence: u [T, d] -> this share's heads' part of Attn(u) [T, d]."""
    z = dims(cfg)
    t = u.shape[0]
    q = _mm(u, p[f"{prefix}/wq/kernel"], quant).reshape(t, z["hq"], z["hd"])
    k = _mm(u, p[f"{prefix}/wk/kernel"], quant).reshape(t, z["hkv"], z["hd"])
    v = _mm(u, p[f"{prefix}/wv/kernel"], quant).reshape(t, z["hkv"], z["hd"])
    inv_freq, scale = rope_parameters(cfg, layer_type, yarn="no_yarn" not in faults)
    q = apply_rope(q, positions, inv_freq, scale)
    k = apply_rope(k, positions, inv_freq, scale)
    group = z["hq"] // z["hkv"]
    k = jnp.repeat(k, group, axis=1)  # query head j reads key-value head j // group
    v = jnp.repeat(v, group, axis=1)
    window = cfg["sliding_window"] if layer_type == "sliding_attention" else None
    if "no_window" in faults:
        window = None
    idx = jnp.arange(t)
    block = min(QUERY_BLOCK, t)

    @jax.checkpoint
    def rows(start):
        qi = lax.dynamic_slice_in_dim(q, start, block, 0)
        i = start + jnp.arange(block)
        scores = jnp.einsum("qhd,khd->hqk", quant(qi) if quant else qi,
                            quant(k) if quant else k, precision=HIGHEST) / math.sqrt(z["hd"])
        seen = idx[None, :] <= i[:, None]
        if "cross_documents" not in faults:
            seg_i = lax.dynamic_slice_in_dim(segment_ids, start, block, 0)
            seen &= seg_i[:, None] == segment_ids[None, :]
        if window is not None:
            seen &= i[:, None] - idx[None, :] < window
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", quant(probs) if quant else probs,
                          quant(v) if quant else v, precision=HIGHEST)

    out = lax.map(rows, jnp.arange(0, t, block)).reshape(t, z["hq"] * z["hd"])
    return _mm(out, p[f"{prefix}/wo/kernel"], quant)


def route(cfg, logits, *, faults):
    """Router logits [T, E_all] -> (weights [T, E_all], zero off the chosen
    experts, and the chosen mask): softmax over all, the k largest,
    renormalised over all the chosen."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    k = cfg["num_experts_per_tok"] - (1 if "top_k_minus_one" in faults else 0)
    top_p, top_e = lax.top_k(probs, k)
    if cfg["norm_topk_prob"] and "no_renorm" not in faults:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    chosen = jnp.sum(jax.nn.one_hot(top_e, probs.shape[-1], dtype=jnp.float32), axis=1)
    weights = jnp.sum(jax.nn.one_hot(top_e, probs.shape[-1], dtype=jnp.float32)
                      * top_p[..., None], axis=1)
    return weights, chosen


def moe(cfg, p, prefix, u, *, quant, faults):
    """u [T, d] -> (this share's experts' part of MoE(u), tokens routed to
    each held expert [E])."""
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
    """One decoder layer on one sequence: (y [T, d], routed counts [E])."""
    eps, prefix, kind = cfg["rms_norm_eps"], f"layers_{i}", layer_types(cfg)[i]
    h = x + attention(cfg, p, f"{prefix}/attn", rms_norm(x, p[f"{prefix}/attn_norm/scale"], eps),
                      segment_ids, positions, kind, quant=quant, faults=faults)
    out, counts = moe(cfg, p, f"{prefix}/moe", rms_norm(h, p[f"{prefix}/moe_norm/scale"], eps),
                      quant=quant, faults=faults)
    return h + out, counts


def attention_part(cfg, p, i, u, segment_ids, positions):
    """The attention sum of layer ``i`` alone, for the share test."""
    return attention(cfg, p, f"layers_{i}/attn", u, segment_ids, positions,
                     layer_types(cfg)[i], quant=None, faults=())


def moe_part(cfg, p, i, u):
    return moe(cfg, p, f"layers_{i}/moe", u, quant=None, faults=())[0]


def sequence_logits(cfg, p: Params, tokens, segment_ids, positions, *, quant=None, faults=()):
    """One sequence: (logits [T, V] over the slice, routed counts [layers, E])."""
    x = p["embed/embedding"][tokens]
    counts = []
    for i in range(cfg["num_hidden_layers"]):
        x, c = jax.checkpoint(
            lambda x, p, i=i: layer(cfg, p, i, x, segment_ids, positions,
                                    quant=quant, faults=faults)
        )(x, p)
        counts.append(c)
    x = rms_norm(x, p["final_norm/scale"], cfg["rms_norm_eps"])
    return _mm(x, p["head/kernel"], quant), jnp.stack(counts)


def sequence_loss(cfg, p, seq, *, quant=None, faults=()):
    """(summed cross-entropy over the positions that have a next token in
    their document, (how many there are, routed counts))."""
    logits, counts = sequence_logits(cfg, p, seq["tokens"], seq["segment_ids"], seq["positions"],
                                     quant=quant, faults=faults)
    has = seq["targets"] >= 0
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, jnp.maximum(seq["targets"], 0)[:, None], axis=-1)[:, 0]
    return -jnp.sum(jnp.where(has, picked, 0.0)), (jnp.sum(has), counts)


def batch_loss_and_grad(cfg, p, batch, *, quant=None, faults=()):
    """Mean cross-entropy of a batch [B, T] of packed sequences, its
    gradient, and the routed counts summed over the sequences: one sequence
    at a time (a scan, so the program holds one sequence's graph)."""
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


# ---------------------------------------------------------------------------
# the optimizer: AdamW, decay on the weight matrices only
# ---------------------------------------------------------------------------


def decayed(name: str) -> bool:
    return name.endswith(("/kernel", "/router", "/w_gate", "/w_up", "/w_down"))


def learning_rate(cfg: dict, count):
    """The rate of update number ``count`` (0 for the first): halving every
    ``lr_decay_steps``, continuously."""
    train = cfg["train"]
    if train["lr_schedule"] != "exponential":
        raise ValueError(f"the reference follows the exponential schedule, not {train['lr_schedule']!r}")
    count = jnp.asarray(count, jnp.float32)
    return train["lr"] * train["lr_decay_rate"] ** (count / train["lr_decay_steps"])


def opt_update(cfg: dict, grads: Params, opt: dict, params: Params, count):
    lr, wd = learning_rate(cfg, count), cfg["train"]["weight_decay"]
    t = jnp.asarray(count, jnp.float32) + 1.0
    mu = {k: B1 * opt["mu"][k] + (1 - B1) * g for k, g in grads.items()}
    nu = {k: B2 * opt["nu"][k] + (1 - B2) * g * g for k, g in grads.items()}
    new = {}
    for k in params:
        step = (mu[k] / (1 - B1**t)) / (jnp.sqrt(nu[k] / (1 - B2**t)) + EPS)
        if wd and decayed(k):
            step = step + wd * params[k]
        new[k] = params[k] - lr * step
    return new, {"mu": mu, "nu": nu}


def first_gradient(cfg: dict, opt_after_one: dict, params0: Optional[Params] = None) -> Params:
    """The first gradient as the optimizer got it, from Adam's first moment
    after one update (both sides go through this same arithmetic)."""
    return {k: v / (1 - B1) for k, v in opt_after_one["mu"].items()}


def make_step(cfg: dict, *, quant: Optional[Callable] = None, faults: Sequence[str] = ()):
    """One jitted update ``(params, opt, batch, count) -> (params, opt, loss,
    routed counts)``. The state is donated: at the timed size the parameters,
    both moments and the gradient are 8.5 GB, and a second copy does not fit
    beside them."""
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
    consumes: pass a copy): the per-step losses,
    the first gradient, the first step's routed counts [layers, E] and the
    parameters after the last step. ``faults`` plants what a wrong program
    would do: ``top_k_minus_one``, ``no_renorm``, ``no_window``, ``no_yarn``,
    ``cross_documents``, ``capacity``, ``drop_half`` (the second half of the
    sequences left out), ``unchanged`` (the state returned as it came)."""
    step = make_step(cfg, quant=quant, faults=faults)
    zeros = jax.jit(lambda p: {k: jnp.zeros_like(v) for k, v in p.items()})
    params = jax.tree.map(jnp.asarray, dict(params))
    opt = {"mu": zeros(params), "nu": zeros(params)}
    losses, grad1, routed1 = [], None, None
    for count, batch in enumerate(batches):
        batch = {k: jnp.asarray(batch[k]) for k in ("tokens", "segment_ids", "positions", "targets")}
        params, opt, loss, counts = step(params, opt, batch, jnp.asarray(count, jnp.int32))
        losses.append(loss)
        if count == 0:
            # to the host at once: a fourth tree does not fit on the chip
            grad1 = first_gradient(cfg, {"mu": jax.device_get(opt["mu"])})
            routed1 = jax.device_get(counts)
    return {"losses": [float(x) for x in losses], "grad1": grad1, "routed1": routed1,
            "params": jax.device_get(params)}
