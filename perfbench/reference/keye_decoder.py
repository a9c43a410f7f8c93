"""The plain reference of the Keye-VL-2.0 language model's decoder
(``model_type: KeyeVL2``,
https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json):
forward, both losses, their gradients and the AdamW update in straightforward
``jax.numpy``, float32, every product at the highest precision. No kernel: the
indexer's scores are written out a block of queries at a time, the selection
is a sort of each row, attention is an explicit mask over the scores.

Each layer, with ``x`` the residual stream, ``u = RMSNorm(x)``, positions
restarting at each packed document and "visible" meaning ``s <= t`` in ``t``'s
document (the equations of ISSUE 30, written here from them and not from the
program):

- ``q_h = RoPE(RMSNorm_q((W_q u)_h))``, ``k_g = RoPE(RMSNorm_k((W_k u)_g))``,
  ``v_g = (W_v u)_g``; plain RoPE at ``rope_theta`` over the whole head;
- the indexer, from ``stop_gradient(u)``: ``qI_j = RoPE((W_Iq u)_j)``,
  ``kI = RoPE(LayerNorm(W_Ik u))``, ``w = W_Iw u / sqrt(J) / sqrt(dI)``,
  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` over the visible ``s``;
- ``S_t``: the ``min(topk, visible)`` visible keys of largest ``I[t, s]``,
  equal scores to the earlier key; no gradient;
- ``o_h = sum_{s in S_t} softmax_{S_t}(q_h . k / sqrt(d))_s v_s``, then
  ``W_o``; the routed experts as the Mellum-2 reference has them;
- ``L = L_lm + L_I``, ``L_I = (1/N) sum_layers sum_t KL(p_t || softmax_{S_t} I[t])``
  with ``p_t`` the attention's probabilities summed over the heads held,
  L1-normalised over ``S_t`` and cut from the graph, ``N`` the step's positions.

The chip's share of a layer as in ``mellum_decoder.py``; where the published
key-value heads are fewer than the shares, share ``s`` of ``n`` holds head
``s * heads // n`` (two shares a head at 4 heads over 8 shares). The indexer
and the router are held whole by every share.

Imports nothing from the program; the experts, the norm, the rotation and the
optimizer are ``mellum_decoder.py``'s.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from perfbench.reference import mellum_decoder as base
from perfbench.reference.mellum_decoder import (  # noqa: F401 - the interface lm_fit uses
    HIGHEST,
    Params,
    _mm,
    apply_rope,
    expert_leaves,
    first_gradient,
    head_leaves,
    opt_update,
    quant_bf16,
    quant_e4m3,
    quant_int8,
    rms_norm,
    router_leaves,
)

# queries a block when the scores are written out: [16, 256, T] float32
QUERY_BLOCK = 256
LAYER_NORM_EPS = 1e-6

FAULTS = ("topk_minus_one", "no_relu", "indexer_sees_later", "align_all_visible",
          "no_qk_norm", "half_indexer_heads")


def dims(cfg: dict) -> dict:
    z = base.dims(cfg)
    sa = cfg["sa_config"]
    z.update(j=sa["indexer_num_heads"], di=sa["indexer_head_dim"], topk=sa["topk"])
    return z


def param_spec(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, kind): the Mellum-2 layer's leaves, the two [head]
    normalisation scales and the indexer's five arrays."""
    z = dims(cfg)
    spec = base.param_spec(cfg)
    for i in range(z["layers"]):
        p = f"layers_{i}/attn"
        spec[f"{p}/q_norm/scale"] = ((z["hd"],), "norm_scale")
        spec[f"{p}/k_norm/scale"] = ((z["hd"],), "norm_scale")
        spec[f"{p}/indexer/wq/kernel"] = ((z["d"], z["j"] * z["di"]), "dense")
        spec[f"{p}/indexer/wk/kernel"] = ((z["d"], z["di"]), "dense")
        spec[f"{p}/indexer/k_norm/scale"] = ((z["di"],), "norm_scale")
        spec[f"{p}/indexer/k_norm/bias"] = ((z["di"],), "norm_bias")
        spec[f"{p}/indexer/w/kernel"] = ((z["d"], z["j"]), "dense")
    return spec


def indexer_leaves(cfg: dict) -> List[str]:
    return sorted(k for k in param_spec(cfg) if "/indexer/" in k)


def share_of(full: Params, cfg_full: dict, n: int, s: int) -> Params:
    """Share ``s`` of ``n`` of the uncut model's leaves: its query heads with
    the key-value head they read (head ``s * heads // n`` where the heads are
    fewer than the shares), its experts, its rows of the vocabulary; norms, the
    router and the indexer whole."""
    z = dims(cfg_full)
    hd, hq, e, v = z["hd"], z["hq"] // n, z["e"] // n, z["v"] // n
    held = max(z["hkv"] // n, 1)
    first = s * z["hkv"] // n
    out = {}
    for name, w in full.items():
        if "/indexer/" in name:
            pass
        elif name.endswith("attn/wq/kernel"):
            w = w[:, s * hq * hd : (s + 1) * hq * hd]
        elif name.endswith(("attn/wk/kernel", "attn/wv/kernel")):
            w = w[:, first * hd : (first + held) * hd]
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
# the attention layer
# ---------------------------------------------------------------------------


def layer_norm(x, scale, bias, eps=LAYER_NORM_EPS):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred * lax.rsqrt(jnp.mean(jnp.square(centred), axis=-1, keepdims=True) + eps) \
        * scale + bias


def rope_frequencies(cfg: dict, dim: int):
    import numpy as np

    inv = float(cfg["rope_theta"]) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    return jnp.asarray(inv, jnp.float32)


def indexer_inputs(cfg, p, prefix, u, positions, *, quant):
    """(qI [T, J, dI], kI [T, dI], w [T, J]) from the layer's normalised
    input, cut from the graph."""
    z = dims(cfg)
    u = lax.stop_gradient(u)
    t = u.shape[0]
    inv = rope_frequencies(cfg, z["di"])
    qi = _mm(u, p[f"{prefix}/wq/kernel"], quant).reshape(t, z["j"], z["di"])
    ki = layer_norm(_mm(u, p[f"{prefix}/wk/kernel"], quant),
                    p[f"{prefix}/k_norm/scale"], p[f"{prefix}/k_norm/bias"])
    qi = apply_rope(qi, positions, inv, 1.0)
    ki = apply_rope(ki[:, None, :], positions, inv, 1.0)[:, 0]
    w = _mm(u, p[f"{prefix}/w/kernel"], None) / math.sqrt(z["j"]) / math.sqrt(z["di"])
    return qi, ki, w


def select(scores, seen, topk: int):
    """[rows, T] scores and visibility -> the rows' selections [rows, T] bool:
    the ``min(topk, visible)`` largest visible scores, equal scores to the
    earlier key. By a sort of each row."""
    masked = jnp.where(seen, scores, -jnp.inf)
    kth = -jnp.sort(-masked, axis=-1)[:, topk - 1] if topk <= masked.shape[-1] \
        else jnp.full(masked.shape[:1], -jnp.inf)
    above = masked > kth[:, None]
    ties = masked == kth[:, None]
    places = topk - jnp.sum(above, axis=-1)
    taken = above | (ties & (jnp.cumsum(ties, axis=-1) <= places[:, None]))
    return taken & seen


def queries_and_keys(cfg, p, prefix, u, positions, *, quant=None, faults=()):
    """(q [T, Hq, hd], k [T, Hq, hd]): normalised per head, rotated; query
    head j reads key-value head j // group, repeated here."""
    z = dims(cfg)
    t, hq, hd = u.shape[0], z["hq"], z["hd"]
    q = _mm(u, p[f"{prefix}/wq/kernel"], quant).reshape(t, hq, hd)
    k = _mm(u, p[f"{prefix}/wk/kernel"], quant).reshape(t, z["hkv"], hd)
    if "no_qk_norm" not in faults:
        q = rms_norm(q, p[f"{prefix}/q_norm/scale"], cfg["rms_norm_eps"])
        k = rms_norm(k, p[f"{prefix}/k_norm/scale"], cfg["rms_norm_eps"])
    inv = rope_frequencies(cfg, hd)
    q, k = apply_rope(q, positions, inv, 1.0), apply_rope(k, positions, inv, 1.0)
    return q, jnp.repeat(k, hq // z["hkv"], axis=1)


def attention(cfg, p, prefix, u, segment_ids, positions, *, quant, faults):
    """One sequence: u [T, d] -> (this share's heads' part of Attn(u) [T, d],
    the rows' KL summed, times each key was selected [T])."""
    z = dims(cfg)
    t, hq, hd, heads, topk = u.shape[0], z["hq"], z["hd"], z["j"], z["topk"]
    q, k = queries_and_keys(cfg, p, prefix, u, positions, quant=quant, faults=faults)
    v = _mm(u, p[f"{prefix}/wv/kernel"], quant).reshape(t, z["hkv"], hd)
    v = jnp.repeat(v, hq // z["hkv"], axis=1)
    qi, ki, w = indexer_inputs(cfg, p, f"{prefix}/indexer", u, positions, quant=quant)
    if "half_indexer_heads" in faults:
        heads //= 2
        qi, w = qi[:, :heads], w[:, :heads]
    if "topk_minus_one" in faults:
        topk -= 1
    idx = jnp.arange(t)
    block = math.gcd(t, QUERY_BLOCK)
    cast = quant if quant else (lambda x: x)

    @jax.checkpoint
    def rows(start, qi, ki, w, q, k, v):
        i = start + jnp.arange(block)
        take = lambda x: lax.dynamic_slice_in_dim(x, start, block, 0)  # noqa: E731
        seen = take(segment_ids)[:, None] == segment_ids[None, :]
        if "indexer_sees_later" not in faults:
            seen &= idx[None, :] <= i[:, None]
        dots = jnp.einsum("qjd,sd->qjs", cast(take(qi)), cast(ki), precision=HIGHEST)
        if "no_relu" not in faults:
            dots = jax.nn.relu(dots)
        scores = jnp.sum(take(w)[:, :, None] * dots, axis=1)
        chosen = select(lax.stop_gradient(scores), seen, topk)
        logits = jnp.einsum("qhd,khd->hqk", cast(take(q)), cast(k), precision=HIGHEST)
        probs = jax.nn.softmax(jnp.where(chosen[None], logits / math.sqrt(hd), -jnp.inf), axis=-1)
        out = jnp.einsum("hqk,khd->qhd", cast(probs), cast(v), precision=HIGHEST)
        # the indexer's target: the heads' probabilities summed, L1-normalised
        target = lax.stop_gradient(jnp.sum(probs, axis=0))
        target = target / jnp.sum(target, axis=-1, keepdims=True)
        over = seen if "align_all_visible" in faults else chosen
        log_q = jax.nn.log_softmax(jnp.where(over, scores, -jnp.inf), axis=-1)
        kl = target * (jnp.log(jnp.where(target > 0, target, 1.0)) - jnp.where(over, log_q, 0.0))
        return out, jnp.sum(kl), jnp.sum(chosen, axis=0)

    out, kl, reads = lax.map(lambda s: rows(s, qi, ki, w, q, k, v), jnp.arange(0, t, block))
    out = _mm(out.reshape(t, hq * hd), p[f"{prefix}/wo/kernel"], quant)
    return out, jnp.sum(kl), jnp.sum(reads, axis=0)


def layer(cfg, p, i, x, segment_ids, positions, *, quant=None, faults=()):
    """One decoder layer on one sequence: (y [T, d], routed counts [E], the
    indexer's loss summed over the rows, times each key was selected [T])."""
    eps, prefix = cfg["rms_norm_eps"], f"layers_{i}"
    attended, kl, reads = attention(
        cfg, p, f"{prefix}/attn", rms_norm(x, p[f"{prefix}/attn_norm/scale"], eps),
        segment_ids, positions, quant=quant, faults=faults)
    h = x + attended
    out, counts = base.moe(cfg, p, f"{prefix}/moe", rms_norm(h, p[f"{prefix}/moe_norm/scale"], eps),
                           quant=quant, faults=faults)
    return h + out, counts, kl, reads


def attention_part(cfg, p, i, u, segment_ids, positions):
    """(the attention sum of layer ``i`` alone, its keys' selection counts),
    for the share test."""
    out, _, reads = attention(cfg, p, f"layers_{i}/attn", u, segment_ids, positions,
                              quant=None, faults=())
    return out, reads


def probability_part(cfg, p, i, u, segment_ids, positions):
    """sum_h A[t, h, s] over the heads this share holds, [T, T]: what the
    shares' sums add up to the uncut layer's, for the share test."""
    z = dims(cfg)
    t, hd, prefix = u.shape[0], z["hd"], f"layers_{i}/attn"
    q, k = queries_and_keys(cfg, p, prefix, u, positions)
    qi, ki, w = indexer_inputs(cfg, p, f"{prefix}/indexer", u, positions, quant=None)
    idx = jnp.arange(t)
    seen = (segment_ids[:, None] == segment_ids[None, :]) & (idx[None, :] <= idx[:, None])
    scores = jnp.sum(w[:, :, None] * jax.nn.relu(
        jnp.einsum("qjd,sd->qjs", qi, ki, precision=HIGHEST)), axis=1)
    chosen = select(scores, seen, z["topk"])
    logits = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / math.sqrt(hd)
    return jnp.sum(jax.nn.softmax(jnp.where(chosen[None], logits, -jnp.inf), axis=-1), axis=0)


moe_part = base.moe_part


def sequence_sums(cfg, p: Params, seq, *, quant=None, faults=()):
    """One sequence: (cross-entropy summed over the positions with a next
    token, the indexer's loss summed over layers and positions), and (routed
    counts [layers, E], selection counts [layers, T])."""
    x = p["embed/embedding"][seq["tokens"]]
    counts, reads, align = [], [], 0.0
    for i in range(cfg["num_hidden_layers"]):
        x, c, kl, r = jax.checkpoint(
            lambda x, p, i=i: layer(cfg, p, i, x, seq["segment_ids"], seq["positions"],
                                    quant=quant, faults=faults)
        )(x, p)
        counts.append(c)
        reads.append(r)
        align = align + kl
    x = rms_norm(x, p["final_norm/scale"], cfg["rms_norm_eps"])
    logits = _mm(x, p["head/kernel"], quant)
    has = seq["targets"] >= 0
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, jnp.maximum(seq["targets"], 0)[:, None], axis=-1)[:, 0]
    return (-jnp.sum(jnp.where(has, picked, 0.0)), align), (jnp.stack(counts), jnp.stack(reads))


def batch_loss_and_grad(cfg, p, batch, *, quant=None, faults=()):
    """(L_lm, L_I), the gradient of their sum, the routed counts and the
    selection counts summed over the batch's sequences: one sequence at a time.
    ``L_lm`` is a mean over the batch's target positions, ``L_I`` over all its
    positions."""
    if "drop_half" in faults:
        batch = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
    n_targets = jnp.maximum(jnp.sum(batch["targets"] >= 0), 1).astype(jnp.float32)
    n_positions = float(batch["tokens"].size)

    def part(seq):
        def objective(q):
            (lm, align), seen = sequence_sums(cfg, q, seq, quant=quant, faults=faults)
            lm, align = lm / n_targets, align / n_positions
            return lm + align, ((lm, align), seen)

        (_, aux), grads = jax.value_and_grad(objective, has_aux=True)(p)
        return aux, grads

    first = {k: v[0] for k, v in batch.items()}
    zero = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(part, first))
    (((lm, align), (counts, reads)), grads), _ = lax.scan(
        lambda acc, seq: (jax.tree.map(jnp.add, acc, part(seq)), None), zero, batch
    )
    return (lm, align), grads, counts, reads


def make_step(cfg: dict, *, quant: Optional[Callable] = None, faults: Sequence[str] = ()):
    """One jitted update ``(params, opt, batch, count) -> (params, opt,
    (L_lm, L_I), routed counts, selection counts)``; the state is donated."""
    faults = tuple(faults)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(p, opt, batch, count):
        losses, grads, counts, reads = batch_loss_and_grad(cfg, p, batch, quant=quant, faults=faults)
        new_p, new_opt = opt_update(cfg, grads, opt, p, count)
        if "unchanged" in faults:
            new_p = jax.tree.map(jnp.copy, p)
        return new_p, new_opt, losses, counts, reads

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
    consumes: pass a copy): the per-step losses (``losses``: the
    cross-entropy; ``align_losses``: the indexer's), the first gradient, the
    first step's routed counts [layers, E] and selection counts [layers, T],
    and the parameters after the last step. ``faults``: ``FAULTS`` here and
    those of ``mellum_decoder.train_steps`` that touch what the two share
    (``top_k_minus_one``, ``no_renorm``, ``capacity``, ``drop_half``,
    ``unchanged``)."""
    step = make_step(cfg, quant=quant, faults=faults)
    zeros = jax.jit(lambda p: {k: jnp.zeros_like(v) for k, v in p.items()})
    params = jax.tree.map(jnp.asarray, dict(params))
    opt = {"mu": zeros(params), "nu": zeros(params)}
    losses, align_losses, grad1, routed1, selected1 = [], [], None, None, None
    for count, batch in enumerate(batches):
        batch = {k: jnp.asarray(batch[k]) for k in ("tokens", "segment_ids", "positions", "targets")}
        params, opt, (lm, align), counts, reads = step(
            params, opt, batch, jnp.asarray(count, jnp.int32))
        losses.append(float(lm))
        align_losses.append(float(align))
        if count == 0:
            # to the host at once: a fourth tree does not fit on the chip
            grad1 = first_gradient(cfg, {"mu": jax.device_get(opt["mu"])})
            routed1, selected1 = jax.device_get(counts), jax.device_get(reads)
    return {"losses": losses, "align_losses": align_losses, "grad1": grad1, "routed1": routed1,
            "selected1": selected1, "params": jax.device_get(params)}
