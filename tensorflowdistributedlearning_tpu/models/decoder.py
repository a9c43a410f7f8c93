"""A causal mixture-of-experts decoder (``backbone="decoder"``): RMSNorm,
grouped-query attention with a rotary embedding by layer type, routed
SiLU-gated experts, an untied head — the layer of, among others, the Mellum-2
family (https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct,
``model_type: mellum``), whose published ``config.json`` keys
``config.py:DecoderConfig`` takes by name. Trained as next-token prediction
over packed documents.

Layer: ``h = x + Attn(RMSNorm(x))``, ``y = h + MLP(RMSNorm(h))``. Attention is
grouped-query, causal and inside one document, with a rotary embedding whose
parameters go by layer type (``DecoderConfig.rope_parameters``; over the whole
head, or over its first ``partial_rotary_factor`` with the rest passed
through) and optionally a per-head RMS normalisation of q and k. What a query
reads goes by layer type too: every earlier key (``full_attention``) or those
inside a window (``sliding_attention``), both ops/blocked_attention.py; or the
``topk`` keys a learned indexer scores highest (``sparse_attention``,
ops/sparse_attention.py: the Keye-VL-2.0 family's layer,
https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B), whose indexer reads the
layer's normalised input cut from the graph and is trained by a loss of its
own, returned beside the cross-entropy. How many query heads a layer has may go
by layer (``num_attention_heads_per_layer``), and with ``gating`` each head's
output is multiplied by a sigmoid gate, one scalar a head and position, from
the layer's normalised input (float32) — both the Laguna family's
(https://huggingface.co/poolside/Laguna-XS.2, ``model_type: laguna``). A
``linear_attention`` layer mixes by Kimi Delta Attention
(``KimiDeltaAttention``: short convolutions, a decay by channel and a
delta-rule recurrence, ops/kda.py) and a ``latent_attention`` layer by
multi-head latent attention without a rotary part (``LatentAttention``) —
the Kimi-Linear family's
(https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct,
``model_type: kimi_linear``).

The MLP goes by ``mlp_layer_types``: ``sparse`` (every layer where the list
is empty) is a float32 router over all experts — a softmax over them or a
sigmoid of each (``scoring_func``) — ``num_experts_per_tok`` a token with
renormalised weights times ``moe_routed_scaling_factor``, SiLU-gated experts,
no token dropped (parallel/expert.py:dropless_experts), and where the
configuration has one a shared expert added unweighted; ``dense`` is one
SiLU-gated MLP of ``intermediate_size``. The head is untied; with targets the
model returns the summed next-token cross-entropy, computed in token chunks so
the ``[tokens, vocabulary]`` logits never stand whole; where it is
differentiated the same pass over a chunk computes the loss's gradients, so
each chunk's logits are computed once (``chunked_head_loss``).

Matrix products run in ``config.dtype`` with float32 accumulation; the
residual stream, norms, rotary embedding, router, head gate, softmaxes and
loss are float32; parameters are float32.

The chip's share of a layer (config.py:DecoderConfig): the module holds the
heads, experts and vocabulary rows its configuration counts, and computes
their part of each sum. On one chip it runs without the exchange that would
complete them.

Recomputation: with 4,096 tokens or more in a step each layer is recomputed in
the backward pass — chosen from the shapes, like the kernels. Kept are a
layer's input and what its attention kernels name (``REMAT_POLICY``): the
forward kernel's output and log-sum-exp, which the backward kernels read, and
a sparse layer's selection, a linear layer's KDA output and the states its
kernel's blocks start from — so the backward pass runs neither the forward
attention or KDA kernel nor the selection's search a second time. Everything else
(projections, the indexer's scores, the indexer's loss, experts) is computed
again; the XLA paths name no attention output (the sparse one its selection
alone), so there a layer keeps its input and no more than that.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tensorflowdistributedlearning_tpu.config import DecoderConfig, ModelConfig
from tensorflowdistributedlearning_tpu.obs import scopes
from tensorflowdistributedlearning_tpu.ops import kda as kda_lib
from tensorflowdistributedlearning_tpu.ops import sparse_attention as sparse_lib
from tensorflowdistributedlearning_tpu.ops.blocked_attention import blocked_attention
from tensorflowdistributedlearning_tpu.parallel import expert as expert_lib

# from this many tokens a step on, layers are recomputed in the backward pass
REMAT_MIN_TOKENS = 4096
# what a recomputed layer keeps beside its input: the values its attention's
# kernels name, whichever its layer type runs
REMAT_POLICY = jax.checkpoint_policies.save_only_these_names(
    sparse_lib.SELECT_NAME, sparse_lib.ATTENTION_NAME, kda_lib.KDA_NAME)
# tokens whose logits stand at once in the head's loss (and, where it is
# differentiated, their gradient: both are computed in one pass over a chunk)
LOSS_CHUNK_TOKENS = 4096
# what a trainer initialises the model on (models.sample_input)
INIT_TOKENS = 8

_INIT = nn.initializers.normal(0.02)


def rope_constants(cfg: DecoderConfig, layer_type: str, dim: int = 0) -> Tuple[np.ndarray, float]:
    """(inv_freq [dim / 2] float32, the factor on cos and sin) of a layer
    type over ``dim`` rotated dimensions (by default the part of the head its
    ``partial_rotary_factor`` names, else the whole head), as
    ``transformers`` computes them: ``default`` is
    theta^(-2i/d); ``yarn`` keeps the fast-rotating dimensions, divides the
    slow ones by ``factor``, blends linearly between the correction
    dimensions of ``beta_fast`` and ``beta_slow``, and scales cos and sin by
    ``attention_factor``."""
    rp = cfg.rope(layer_type)
    dim, theta = dim or cfg.rotary_dim(layer_type), float(rp["rope_theta"])
    inv = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rp["rope_type"] == "default":
        return inv.astype(np.float32), 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(f"Unknown rope_type {rp['rope_type']!r}")
    factor, original = float(rp["factor"]), float(rp["original_max_position_embeddings"])

    def correction_dim(rotations: float) -> float:
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(rp["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rp["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    inv = inv / factor * ramp + inv * (1.0 - ramp)
    scale = rp.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return inv.astype(np.float32), float(scale)


def apply_rope(x: jax.Array, positions: jax.Array, inv_freq, scale: float) -> jax.Array:
    """x [B, T, H, hd] float32, positions [B, T]: rotate-half over the first
    ``2 * len(inv_freq)`` dimensions of a head; the rest pass as they are."""
    rotated_dims = 2 * len(inv_freq)
    if rotated_dims < x.shape[-1]:
        first = apply_rope(x[..., :rotated_dims], positions, inv_freq, scale)
        return jnp.concatenate([first, x[..., rotated_dims:]], axis=-1)
    angles = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    emb = jnp.concatenate([angles, angles], axis=-1)[:, :, None, :]
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * (jnp.cos(emb) * scale) + rotated * (jnp.sin(emb) * scale)


class RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x = x.astype(jnp.float32)
        return scale * x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps)


class Projection(nn.Module):
    """``x @ kernel`` with no bias: operands in ``dtype``, float32 out;
    ``precise``: float32 operands at the highest precision, like the router."""

    features: int
    dtype: Any
    precise: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        kernel = self.param("kernel", _INIT, (x.shape[-1], self.features), jnp.float32)
        if self.precise:
            return jnp.dot(x.astype(jnp.float32), kernel, precision=lax.Precision.HIGHEST)
        return jnp.dot(
            x.astype(self.dtype), kernel.astype(self.dtype), preferred_element_type=jnp.float32
        )


class LayerNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],), jnp.float32)
        x = x.astype(jnp.float32)
        centred = x - jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(centred), axis=-1, keepdims=True)
        return centred * lax.rsqrt(var + self.eps) * scale + bias


class Indexer(nn.Module):
    """What scores a ``sparse_attention`` layer's keys: per position
    ``indexer_num_heads`` small queries, one small key (LayerNorm, rotated
    like the queries, over all its dimensions) and a weight a head, from the
    layer's normalised input cut from the graph. Products in ``dtype``; the
    weights' in float32 like the router's."""

    cfg: DecoderConfig
    layer_type: str
    dtype: Any

    @nn.compact
    def __call__(self, u, positions):
        sa = self.cfg.indexer
        heads, dim = sa["indexer_num_heads"], sa["indexer_head_dim"]
        b, t, _ = u.shape
        u = lax.stop_gradient(u)
        qi = Projection(heads * dim, self.dtype, name="wq")(u).reshape(b, t, heads, dim)
        ki = LayerNorm(1e-6, name="k_norm")(Projection(dim, self.dtype, name="wk")(u))
        inv_freq, scale = rope_constants(self.cfg, self.layer_type, dim)
        qi = apply_rope(qi, positions, inv_freq, scale).astype(self.dtype)
        ki = apply_rope(ki[:, :, None, :], positions, inv_freq, scale)[:, :, 0].astype(self.dtype)
        wi = Projection(heads, jnp.float32, precise=True, name="w")(u) * (heads**-0.5 * dim**-0.5)
        return qi, ki, wi


_SCOPES = {"sliding_attention": "decoder/attn_sliding", "full_attention": "decoder/attn_full",
           "sparse_attention": "decoder/attn_sparse"}


class DecoderAttention(nn.Module):
    """``(attention's part of the residual update, extras)`` with ``heads``
    query heads. A ``sparse_attention`` layer adds to ``extras`` ``align`` (the
    indexer's loss summed over the positions), ``reads`` (how many queries
    read each key position [T]) and ``searched`` (the columns and the blocks
    of rows the selection's searches ran over); a gated layer ``gate`` (the
    gates summed over positions and heads)."""

    cfg: DecoderConfig
    layer_type: str
    dtype: Any
    heads: int = 0  # the layer's own count; 0: cfg.num_attention_heads

    @nn.compact
    def __call__(self, u, segment_ids, positions):
        cfg = self.cfg
        b, t, _ = u.shape
        hq, hkv, hd = self.heads or cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        with scopes.scope(_SCOPES[self.layer_type]):
            # the projections, q/k normalisation and rotary apart from the
            # block's kernels
            with scopes.scope("decoder/attn_proj"):
                q = Projection(hq * hd, self.dtype, name="wq")(u).reshape(b, t, hq, hd)
                k = Projection(hkv * hd, self.dtype, name="wk")(u).reshape(b, t, hkv, hd)
                v = Projection(hkv * hd, self.dtype, name="wv")(u).reshape(b, t, hkv, hd)
                if cfg.use_qk_norm:
                    q = RMSNorm(cfg.rms_norm_eps, name="q_norm")(q)
                    k = RMSNorm(cfg.rms_norm_eps, name="k_norm")(k)
                inv_freq, scale = rope_constants(cfg, self.layer_type)
                q = apply_rope(q, positions, inv_freq, scale).astype(self.dtype)
                k = apply_rope(k, positions, inv_freq, scale).astype(self.dtype)
            extras = {}
            if self.layer_type == "sparse_attention":
                with scopes.scope("decoder/attn_proj"):
                    qi, ki, wi = Indexer(cfg, self.layer_type, self.dtype, name="indexer")(
                        u, positions)
                out, *extra = sparse_lib.sparse_attention(
                    q, k, v.astype(self.dtype), qi, ki, wi, segment_ids,
                    topk=cfg.indexer["topk"],
                )
                extras = dict(zip(("align", "reads", "searched"), extra))
            else:
                out = blocked_attention(
                    q, k, v.astype(self.dtype), segment_ids,
                    window=cfg.sliding_window if self.layer_type == "sliding_attention" else None,
                )
            if cfg.gating:
                with scopes.scope("decoder/attn_gate"):
                    gate = jax.nn.sigmoid(
                        Projection(hq, jnp.float32, precise=True, name="head_gate")(u))
                    out = out.astype(jnp.float32) * gate[..., None]
                    extras["gate"] = jnp.sum(gate)
            with scopes.scope("decoder/attn_proj"):
                out = Projection(cfg.hidden_size, self.dtype, name="wo")(
                    out.reshape(b, t, hq * hd))
            return out, extras


class ShortConvolution(nn.Module):
    """A causal depthwise convolution over ``kernel_size`` positions with no
    bias, inside each document: ``y_t = sum_j w[K - 1 - j] x_{t - j}`` over the
    ``j <= positions_t``. float32."""

    kernel_size: int

    @nn.compact
    def __call__(self, x, positions):
        w = self.param("kernel", _INIT, (self.kernel_size, x.shape[-1]), jnp.float32)
        out = x * w[-1]
        for j in range(1, self.kernel_size):
            earlier = jnp.pad(x[:, :-j], ((0, 0), (j, 0), (0, 0)))
            out = out + jnp.where((positions >= j)[..., None], earlier, 0.0) * w[-1 - j]
        return out


def _l2_normalize(x, eps: float = 1e-6):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def _inverse_softplus(y):
    return y + jnp.log(-jnp.expm1(-y))


class KimiDeltaAttention(nn.Module):
    """``(the layer's part of the residual update, extras)`` of a
    ``linear_attention`` layer — Kimi Delta Attention as the family's
    modelling code has it, with ``h`` heads of ``d`` channels
    (``linear_attn_config``):

    - ``q, k = L2norm(SiLU(conv(u W_q|k)))``, ``v = SiLU(conv(u W_v))``, each
      convolution causal, depthwise, without bias and inside the document;
    - the decay by channel ``g = -exp(A_log_h) softplus(u W_fa W_fb +
      dt_bias)`` and ``beta = sigmoid(u W_b)``, a scalar a head;
    - the recurrence of ops/kda.py with ``q`` scaled by ``d^-1/2``;
    - ``W_o concat_h(RMSNorm_d(o_h) * sigmoid(u W_ga W_gb)_h)``.

    The projections to q, k, v and back run in ``dtype``; the decay, beta and
    the output gate's in float32 at the highest precision, like the router.
    ``extras["decay"]`` is the sum of ``exp(g)`` over positions, heads and
    channels."""

    cfg: DecoderConfig
    dtype: Any

    @nn.compact
    def __call__(self, u, positions):
        cfg, lin = self.cfg, self.cfg.linear
        b, t, _ = u.shape
        h, d, width = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
        with scopes.scope("decoder/attn_linear"):
            with scopes.scope("decoder/attn_linear/proj"):
                q, k, v = (Projection(h * d, self.dtype, name=f"{w}_proj")(u) for w in "qkv")
                f = Projection(h * d, jnp.float32, precise=True, name="f_b_proj")(
                    Projection(d, jnp.float32, precise=True, name="f_a_proj")(u))
                beta = jax.nn.sigmoid(Projection(h, jnp.float32, precise=True, name="b_proj")(u))
            with scopes.scope("decoder/attn_linear/conv"):
                q, k, v = (jax.nn.silu(ShortConvolution(width, name=f"{w}_conv1d")(x, positions))
                           for w, x in zip("qkv", (q, k, v)))
                a_log = self.param("A_log", lambda key, s: jnp.log(
                    jax.random.uniform(key, s, jnp.float32, 1.0, 16.0)), (h,))
                dt_bias = self.param("dt_bias", lambda key, s: _inverse_softplus(jnp.exp(
                    jax.random.uniform(key, s, jnp.float32, math.log(1e-3), math.log(1e-1)))),
                    (h * d,))
                g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                    (f + dt_bias).reshape(b, t, h, d))
                q = (_l2_normalize(q.reshape(b, t, h, d)) * d**-0.5).astype(self.dtype)
                k = _l2_normalize(k.reshape(b, t, h, d)).astype(self.dtype)
                v = v.reshape(b, t, h, d).astype(self.dtype)
            o = kda_lib.kda(q, k, v, g, beta, positions)  # in its own scope, .../kda
            with scopes.scope("decoder/attn_linear/out_norm"):
                gate = Projection(h * d, jnp.float32, precise=True, name="g_b_proj")(
                    Projection(d, jnp.float32, precise=True, name="g_a_proj")(u))
                o = RMSNorm(cfg.rms_norm_eps, name="o_norm")(o) * jax.nn.sigmoid(
                    gate.reshape(b, t, h, d))
            with scopes.scope("decoder/attn_linear/proj"):
                out = Projection(cfg.hidden_size, self.dtype, name="o_proj")(o.reshape(b, t, h * d))
            return out, {"decay": jnp.sum(jnp.exp(g))}


class LatentAttention(nn.Module):
    """``(the layer's part of the residual update, extras)`` of a
    ``latent_attention`` layer: multi-head latent attention without a rotary
    part (``mla_use_nope``). ``q_h = u W_q`` is ``[nope, rope]`` wide; ``c =
    u W_kva`` is ``kv_lora_rank + qk_rope_head_dim`` wide, ``[k_nope_h, v_h] =
    RMSNorm(c[:rank]) W_kvb`` and ``k_pe = c[rank:]`` is every head's, not
    rotated. Causal softmax inside the document over ``q_h . [k_nope_h,
    k_pe]`` scaled by the query head's size (ops/blocked_attention.py: queries
    and keys wider than the values), then ``W_o concat_h``."""

    cfg: DecoderConfig
    dtype: Any

    @nn.compact
    def __call__(self, u, segment_ids):
        cfg = self.cfg
        b, t, _ = u.shape
        h, nope, rope = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        rank, vd = cfg.kv_lora_rank, cfg.v_head_dim
        with scopes.scope("decoder/attn_latent"):
            q = Projection(h * (nope + rope), self.dtype, name="q_proj")(u).reshape(
                b, t, h, nope + rope)
            c = Projection(rank + rope, self.dtype, name="kv_a_proj_with_mqa")(u)
            kv = Projection(h * (nope + vd), self.dtype, name="kv_b_proj")(
                RMSNorm(cfg.rms_norm_eps, name="kv_a_layernorm")(c[..., :rank])
            ).reshape(b, t, h, nope + vd)
            k_pe = jnp.broadcast_to(c[:, :, None, rank:], (b, t, h, rope))
            k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
            with scopes.scope("decoder/attn_latent/attend"):
                out = blocked_attention(q.astype(self.dtype), k.astype(self.dtype),
                                        kv[..., nope:].astype(self.dtype), segment_ids)
            out = Projection(cfg.hidden_size, self.dtype, name="o_proj")(out.reshape(b, t, h * vd))
            return out, {}


class GatedMLP(nn.Module):
    """``(SiLU(u W_gate) * (u W_up)) W_down``: a ``dense`` layer's MLP and the
    shared expert of a ``sparse`` one."""

    features: int
    dtype: Any

    @nn.compact
    def __call__(self, u):
        gate = Projection(self.features, self.dtype, name="w_gate")(u)
        up = Projection(self.features, self.dtype, name="w_up")(u)
        return Projection(u.shape[-1], self.dtype, name="w_down")(jax.nn.silu(gate) * up)


class DecoderMoE(nn.Module):
    cfg: DecoderConfig
    dtype: Any

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        d, f, held = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
        total = held * cfg.share_count
        router = self.param("router", _INIT, (d, total), jnp.float32)
        w_gate = self.param("w_gate", _INIT, (held, d, f), jnp.float32)
        w_up = self.param("w_up", _INIT, (held, d, f), jnp.float32)
        w_down = self.param("w_down", _INIT, (held, f, d), jnp.float32)
        x = u.reshape(-1, d)
        with scopes.scope("decoder/moe/route"):
            logits = jnp.dot(x, router, precision=lax.Precision.HIGHEST)
            weights, experts = expert_lib.top_k_routing(
                logits, cfg.num_experts_per_tok, cfg.norm_topk_prob,
                score=cfg.scoring_func, scale=cfg.moe_routed_scaling_factor,
            )
        with scopes.scope("decoder/moe/experts"):
            out, counts, dropped = expert_lib.dropless_experts(
                x.astype(self.dtype), weights, experts,
                w_gate.astype(self.dtype), w_up.astype(self.dtype), w_down.astype(self.dtype),
                num_experts_total=total, first_expert=cfg.share_index * held,
            )
        out = out.reshape(u.shape)
        if cfg.shared_expert_intermediate_size:
            with scopes.scope("decoder/moe/shared"):
                out = out + GatedMLP(cfg.shared_expert_intermediate_size, self.dtype,
                                     name="shared")(u)
        buffer_rows = expert_lib.pair_buffer_rows(counts, experts.size, total)
        return out, counts, dropped, buffer_rows, expert_lib.row_tile_visits(counts)


class DecoderLayer(nn.Module):
    """Layer ``index``: ``(y, (the expert layer's counters — none on a
    ``dense`` layer, attention's extras))``."""

    cfg: DecoderConfig
    index: int
    dtype: Any

    @nn.compact
    def __call__(self, x, segment_ids, positions):
        cfg, eps = self.cfg, self.cfg.rms_norm_eps
        with scopes.scope("decoder/norm"):
            u = RMSNorm(eps, name="attn_norm")(x)
        kind = cfg.layer_types[self.index]
        if kind == "linear_attention":
            attended, extras = KimiDeltaAttention(cfg, self.dtype, name="attn")(u, positions)
        elif kind == "latent_attention":
            attended, extras = LatentAttention(cfg, self.dtype, name="attn")(u, segment_ids)
        else:
            attended, extras = DecoderAttention(
                cfg, kind, self.dtype, cfg.heads(self.index), name="attn"
            )(u, segment_ids, positions)
        h = x + attended
        if cfg.mlp_type(self.index) == "dense":
            with scopes.scope("decoder/norm"):
                u = RMSNorm(eps, name="mlp_norm")(h)
            with scopes.scope("decoder/mlp_dense"):
                out = GatedMLP(cfg.intermediate_size, self.dtype, name="mlp")(u)
            return h + out, ((), extras)
        with scopes.scope("decoder/norm"):
            u = RMSNorm(eps, name="moe_norm")(h)
        out, *counters = DecoderMoE(cfg, self.dtype, name="moe")(u)
        return h + out, (tuple(counters), extras)


def _head_chunks(h: jax.Array, targets: jax.Array):
    """h [tokens, d] and targets [tokens] as the head's scan takes them."""
    chunk = math.gcd(h.shape[0], LOSS_CHUNK_TOKENS)
    return h.reshape(-1, chunk, h.shape[-1]), targets.reshape(-1, chunk)


def _chunk_loss(hc, w, tc):
    """One chunk's summed cross-entropy over the positions that have a target
    and the arg-max hits among them, and what its gradient is made of: the
    float32 logits, their log-sum-exp, where each row's target is and which
    rows have one. The picked logit and the arg-max (the first index that
    holds the row's maximum) are float32 sums and minima over the row like the
    sum of exponentials, so XLA reads the logits for all three in one pass, and
    the maximum fuses into the product."""
    logits = jnp.dot(hc, w, preferred_element_type=jnp.float32)
    has = tc >= 0
    lse = jax.nn.logsumexp(logits, axis=-1)
    column = lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    is_target = column == tc[:, None]
    picked = jnp.sum(jnp.where(is_target, logits, 0.0), axis=-1)
    top = jnp.max(logits, axis=-1, keepdims=True)
    best = jnp.min(jnp.where(logits == top, column.astype(jnp.float32), float(logits.shape[1])),
                   axis=-1)
    loss = jnp.sum(jnp.where(has, lse - picked, 0.0))
    hits = jnp.sum(jnp.where(has, best == tc.astype(jnp.float32), False))
    return loss, hits.astype(jnp.float32), (logits, lse, is_target, has)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def chunked_head_loss(dtype, h: jax.Array, kernel: jax.Array, targets: jax.Array):
    """(summed cross-entropy, arg-max hits) of ``h [tokens, d] @ kernel [d, V]``
    against ``targets [tokens]`` (-1: none), a chunk of tokens at a time;
    products in ``dtype`` with float32 accumulation, the rest float32. Called
    as it stands it computes the loss alone, one product a chunk.
    Differentiated, one pass over the chunks (``_head_loss_fwd``) computes the
    loss and both gradients, so no logits are kept or computed twice."""
    w = kernel.astype(dtype)

    def one(carry, xs):
        loss, hits, _ = _chunk_loss(xs[0].astype(dtype), w, xs[1])
        return (carry[0] + loss, carry[1] + hits), None

    zero = jnp.zeros((), jnp.float32)
    return lax.scan(one, (zero, zero), _head_chunks(h, targets))[0]


def _head_loss_fwd(dtype, h, kernel, targets):
    """The loss pass that also forms, from each chunk's logits and log-sum-exp,
    ``dlogits = (softmax - onehot(target)) * has_target`` (float32) and the two
    products it owes: the chunk's ``dlogits @ W^T`` and ``h^T @ dlogits`` summed
    over the chunks in float32. The gradients at a cotangent of 1 are the
    residuals; nothing ``[tokens, V]`` outlives its chunk."""
    w = kernel.astype(dtype)

    def one(carry, xs):
        hc, tc = xs[0].astype(dtype), xs[1]
        loss, hits, (logits, lse, is_target, has) = _chunk_loss(hc, w, tc)
        dlogits = jnp.where(has[:, None], jnp.exp(logits - lse[:, None]) - is_target, 0.0)
        dh = lax.dot_general(dlogits, w, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        dw = lax.dot_general(hc, dlogits, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        return (carry[0] + loss, carry[1] + hits, carry[2] + dw), dh.astype(h.dtype)

    zero = jnp.zeros((), jnp.float32)
    (loss_sum, hits, dw), dh = lax.scan(
        one, (zero, zero, jnp.zeros(kernel.shape, jnp.float32)), _head_chunks(h, targets)
    )
    return (loss_sum, hits), (dh.reshape(h.shape), dw.astype(kernel.dtype))


def _head_loss_bwd(dtype, residuals, cotangents):
    dh, dw = residuals
    g = cotangents[0]  # the hits are piecewise constant; integer targets take none
    return (g * dh).astype(dh.dtype), (g * dw).astype(dw.dtype), None


chunked_head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)


class HeadLoss(nn.Module):
    """The untied vocabulary head and the next-token cross-entropy over it,
    ``LOSS_CHUNK_TOKENS`` tokens at a time (``chunked_head_loss``): where the
    loss is differentiated each chunk's logits are computed once, and the
    gradients of the hidden states and of the kernel in the same pass."""

    vocab_size: int
    dtype: Any

    @nn.compact
    def __call__(self, h: jax.Array, targets: jax.Array) -> Dict[str, jax.Array]:
        d = h.shape[-1]
        kernel = self.param("kernel", _INIT, (d, self.vocab_size), jnp.float32)
        targets = targets.reshape(-1)
        with scopes.scope("decoder/head_loss"):
            loss_sum, hits = chunked_head_loss(self.dtype, h.reshape(-1, d), kernel, targets)
        return {
            "loss_sum": loss_sum,
            "n_targets": jnp.sum(targets >= 0).astype(jnp.float32),
            "n_correct": hits,
        }


class MoEDecoder(nn.Module):
    """``inputs``: a dict of ``tokens``, ``segment_ids``, ``positions`` and
    optionally ``targets`` (each [B, T] int32, data/tokens.py), or the tokens
    alone (each row one whole document, every next token a target). With targets the result is the loss
    sums and the step's counters (train/step.py:SequenceTask reads them);
    without, the final normalised hidden states."""

    config: ModelConfig

    @nn.compact
    def __call__(self, inputs, train: bool = False):
        cfg = self.config.decoder
        dtype = jnp.dtype(self.config.dtype)
        if not isinstance(inputs, dict):
            tokens = jnp.asarray(inputs)
            inputs = {
                "tokens": tokens,
                "segment_ids": jnp.zeros_like(tokens),
                "positions": jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape),
                "targets": jnp.concatenate(
                    [tokens[:, 1:], jnp.full_like(tokens[:, :1], -1)], axis=1
                ),
            }
        tokens, segment_ids, positions = (
            inputs["tokens"], inputs["segment_ids"], inputs["positions"]
        )
        b, t = tokens.shape
        with scopes.scope("decoder/embed"):
            x = nn.Embed(cfg.vocab_size, cfg.hidden_size, embedding_init=_INIT,
                         name="embed")(tokens)
        kinds = cfg.layer_types[: cfg.num_hidden_layers]
        layer_cls = DecoderLayer
        if b * t >= REMAT_MIN_TOKENS:
            layer_cls = nn.remat(DecoderLayer, policy=REMAT_POLICY)
        # the expert layers' counters, a row a sparse layer (a dense one adds none)
        counts, buffer_rows, dropped = [], [], jnp.zeros((), jnp.int32)
        tile_visits = jnp.zeros((), jnp.int32)
        align, reads, searched = jnp.zeros((), jnp.float32), [], []
        gate_sums, gate_counts = {}, {}  # a gated model's, by layer type
        decay = jnp.zeros((), jnp.float32)  # exp(g) summed over the linear layers
        for i, kind in enumerate(kinds):
            x, (counters, extras) = layer_cls(cfg, i, dtype, name=f"layers_{i}")(
                x, segment_ids, positions
            )
            if counters:
                c, d, r, v = counters
                counts.append(c)
                buffer_rows.append(r)
                dropped = dropped + d
                tile_visits = tile_visits + v
            if "align" in extras:
                align = align + extras["align"]
                reads.append(extras["reads"])
                searched.append(extras["searched"])
            if "decay" in extras:
                decay = decay + extras["decay"]
            if "gate" in extras:
                short = kind.split("_")[0]
                gate_sums[short] = gate_sums.get(short, 0.0) + extras["gate"]
                gate_counts[short] = gate_counts.get(short, 0) + b * t * cfg.heads(i)
        with scopes.scope("decoder/norm"):
            x = RMSNorm(cfg.rms_norm_eps, name="final_norm")(x)
        if "targets" not in inputs:
            return {"hidden": x}
        out = HeadLoss(cfg.vocab_size, dtype, name="head")(x, inputs["targets"])
        # keys a query reads, summed over the positions, by the layer types
        # the model has: its document's earlier positions and itself; inside
        # the window on sliding layers; the selection on sparse layers (a
        # layer's mean)
        seen = positions.astype(jnp.float32) + 1.0
        attn_keys = {
            "full_attention": lambda: jnp.sum(seen),
            "sliding_attention": lambda: jnp.sum(jnp.minimum(seen, float(cfg.sliding_window))),
            "sparse_attention": lambda: jnp.sum(jnp.stack(reads)) / len(reads),
            "latent_attention": lambda: jnp.sum(seen),
        }
        out.update(
            expert_tokens=jnp.stack(counts).astype(jnp.float32),
            pairs_dropped=dropped.astype(jnp.float32),
            buffer_rows=jnp.stack(buffer_rows).astype(jnp.float32),
            # row tiles of the grouped products that the held experts' groups
            # overlap, every sparse layer together
            tile_visits=tile_visits.astype(jnp.float32),
            **{f"attn_keys_{kind.split('_')[0]}": attn_keys[kind]()
               for kind in sorted(set(kinds)) if kind in attn_keys},
            # a gated model's gates by layer type: their sum, and how many
            **{f"attn_gate_sum_{kind}": total for kind, total in gate_sums.items()},
            **{f"attn_gate_n_{kind}": jnp.asarray(n, jnp.float32)
               for kind, n in gate_counts.items()},
            n_sequences=jnp.asarray(b, jnp.float32),
            n_positions=jnp.asarray(b * t, jnp.float32),
        )
        if "linear_attention" in kinds:
            # the decays exp(g) summed over the linear layers' positions, heads
            # and channels, and how many there are
            lin = cfg.linear
            out.update(kda_decay_sum=decay, kda_decay_n=jnp.asarray(
                b * t * kinds.count("linear_attention") * lin["num_heads"] * lin["head_dim"],
                jnp.float32))
        if "sparse_attention" in kinds:
            # the indexer's loss, the pairs its layers scored, how many
            # queries read each key position, by sparse layer [layers, T], and
            # what the selections searched, all sparse layers together
            out.update(
                align_sum=align,
                sparse_pairs_scored=jnp.sum(seen) * len(reads),
                sparse_key_reads=jnp.stack(reads),
                sparse_select_columns=sum(x["columns"] for x in searched),
                sparse_tie_blocks=sum(x["tie_blocks"] for x in searched),
            )
        return out
