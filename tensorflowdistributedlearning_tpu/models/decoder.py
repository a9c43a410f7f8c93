"""A causal mixture-of-experts decoder (``backbone="decoder"``): RMSNorm,
grouped-query attention with a rotary embedding by layer type, routed
SiLU-gated experts, an untied head — the layer of, among others, the Mellum-2
family (https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct,
``model_type: mellum``), whose published ``config.json`` keys
``config.py:DecoderConfig`` takes by name. Trained as next-token prediction
over packed documents.

Layer: ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``. Attention is
grouped-query with a rotary embedding whose parameters go by layer type
(``sliding_attention``: plain RoPE and a window; ``full_attention``: YaRN),
causal and inside one document (ops/blocked_attention.py). Every MLP is
sparse: a float32 router over all experts, ``num_experts_per_tok`` a token
with renormalised weights, SiLU-gated experts, no token dropped
(parallel/expert.py:dropless_experts). The head is untied; with targets the
model returns the summed next-token cross-entropy, computed in token chunks so
the ``[tokens, vocabulary]`` logits never stand whole.

Matrix products run in ``config.dtype`` with float32 accumulation; the
residual stream, norms, rotary embedding, router, softmaxes and loss are
float32; parameters are float32.

The chip's share of a layer (config.py:DecoderConfig): the module holds the
heads, experts and vocabulary rows its configuration counts, and computes
their part of each sum. On one chip it runs without the exchange that would
complete them.

Recomputation: with 4,096 tokens or more in a step each layer is recomputed in
the backward pass (only the layers' inputs are kept) — chosen from the shapes,
like the kernels.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tensorflowdistributedlearning_tpu.config import DecoderConfig, ModelConfig
from tensorflowdistributedlearning_tpu.ops.blocked_attention import blocked_attention
from tensorflowdistributedlearning_tpu.parallel import expert as expert_lib

# from this many tokens a step on, layers are recomputed in the backward pass
REMAT_MIN_TOKENS = 4096
# tokens whose logits stand at once in the head's loss
LOSS_CHUNK_TOKENS = 4096
# what a trainer initialises the model on (models.sample_input)
INIT_TOKENS = 8

_INIT = nn.initializers.normal(0.02)


def rope_constants(cfg: DecoderConfig, layer_type: str) -> Tuple[np.ndarray, float]:
    """(inv_freq [head_dim / 2] float32, the factor on cos and sin) of a layer
    type, as ``transformers`` computes them: ``default`` is
    theta^(-2i/d); ``yarn`` keeps the fast-rotating dimensions, divides the
    slow ones by ``factor``, blends linearly between the correction
    dimensions of ``beta_fast`` and ``beta_slow``, and scales cos and sin by
    ``attention_factor``."""
    rp = cfg.rope(layer_type)
    dim, theta = cfg.head_dim, float(rp["rope_theta"])
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
    """x [B, T, H, hd] float32, positions [B, T]: rotate-half."""
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
    """``x @ kernel`` with no bias: operands in ``dtype``, float32 out."""

    features: int
    dtype: Any

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        kernel = self.param("kernel", _INIT, (x.shape[-1], self.features), jnp.float32)
        return jnp.dot(
            x.astype(self.dtype), kernel.astype(self.dtype), preferred_element_type=jnp.float32
        )


class DecoderAttention(nn.Module):
    cfg: DecoderConfig
    layer_type: str
    dtype: Any

    @nn.compact
    def __call__(self, u, segment_ids, positions):
        cfg = self.cfg
        b, t, _ = u.shape
        hq, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        sliding = self.layer_type == "sliding_attention"
        with jax.named_scope("decoder/attn_sliding" if sliding else "decoder/attn_full"):
            q = Projection(hq * hd, self.dtype, name="wq")(u).reshape(b, t, hq, hd)
            k = Projection(hkv * hd, self.dtype, name="wk")(u).reshape(b, t, hkv, hd)
            v = Projection(hkv * hd, self.dtype, name="wv")(u).reshape(b, t, hkv, hd)
            inv_freq, scale = rope_constants(cfg, self.layer_type)
            q = apply_rope(q, positions, inv_freq, scale).astype(self.dtype)
            k = apply_rope(k, positions, inv_freq, scale).astype(self.dtype)
            out = blocked_attention(
                q, k, v.astype(self.dtype), segment_ids,
                window=cfg.sliding_window if sliding else None,
            )
            return Projection(cfg.hidden_size, self.dtype, name="wo")(out.reshape(b, t, hq * hd))


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
        with jax.named_scope("decoder/moe/route"):
            logits = jnp.dot(x, router, precision=lax.Precision.HIGHEST)
            weights, experts = expert_lib.top_k_routing(
                logits, cfg.num_experts_per_tok, cfg.norm_topk_prob
            )
        with jax.named_scope("decoder/moe/experts"):
            out, counts, dropped = expert_lib.dropless_experts(
                x.astype(self.dtype), weights, experts,
                w_gate.astype(self.dtype), w_up.astype(self.dtype), w_down.astype(self.dtype),
                num_experts_total=total, first_expert=cfg.share_index * held,
            )
        buffer_rows = expert_lib.pair_buffer_rows(counts, experts.size, total)
        return out.reshape(u.shape), counts, dropped, buffer_rows


class DecoderLayer(nn.Module):
    cfg: DecoderConfig
    layer_type: str
    dtype: Any

    @nn.compact
    def __call__(self, x, segment_ids, positions):
        eps = self.cfg.rms_norm_eps
        h = x + DecoderAttention(self.cfg, self.layer_type, self.dtype, name="attn")(
            RMSNorm(eps, name="attn_norm")(x), segment_ids, positions
        )
        out, *counters = DecoderMoE(self.cfg, self.dtype, name="moe")(
            RMSNorm(eps, name="moe_norm")(h)
        )
        return h + out, counters


class HeadLoss(nn.Module):
    """The untied vocabulary head and the next-token cross-entropy over it,
    ``LOSS_CHUNK_TOKENS`` tokens at a time; each chunk's logits are recomputed
    in the backward pass."""

    vocab_size: int
    dtype: Any

    @nn.compact
    def __call__(self, h: jax.Array, targets: jax.Array) -> Dict[str, jax.Array]:
        d = h.shape[-1]
        kernel = self.param("kernel", _INIT, (d, self.vocab_size), jnp.float32)
        w = kernel.astype(self.dtype)
        h, targets = h.reshape(-1, d), targets.reshape(-1)
        chunk = math.gcd(h.shape[0], LOSS_CHUNK_TOKENS)

        @jax.checkpoint
        def one(carry, xs):
            hc, tc = xs
            logits = jnp.dot(hc.astype(self.dtype), w, preferred_element_type=jnp.float32)
            has = tc >= 0
            picked = jnp.take_along_axis(logits, jnp.maximum(tc, 0)[:, None], axis=-1)[:, 0]
            loss = jnp.sum(jnp.where(has, jax.nn.logsumexp(logits, axis=-1) - picked, 0.0))
            hits = jnp.sum(jnp.where(has, jnp.argmax(logits, axis=-1) == tc, False))
            return (carry[0] + loss, carry[1] + hits.astype(jnp.float32)), None

        with jax.named_scope("decoder/head_loss"):
            zero = jnp.zeros((), jnp.float32)
            (loss_sum, hits), _ = lax.scan(
                one, (zero, zero), (h.reshape(-1, chunk, d), targets.reshape(-1, chunk))
            )
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
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, embedding_init=_INIT, name="embed")(tokens)
        layer_cls = nn.remat(DecoderLayer) if b * t >= REMAT_MIN_TOKENS else DecoderLayer
        counts, buffer_rows, dropped = [], [], jnp.zeros((), jnp.int32)
        for i in range(cfg.num_hidden_layers):
            x, (c, d, r) = layer_cls(cfg, cfg.layer_types[i], dtype, name=f"layers_{i}")(
                x, segment_ids, positions
            )
            counts.append(c)
            buffer_rows.append(r)
            dropped = dropped + d
        x = RMSNorm(cfg.rms_norm_eps, name="final_norm")(x)
        if "targets" not in inputs:
            return {"hidden": x}
        out = HeadLoss(cfg.vocab_size, dtype, name="head")(x, inputs["targets"])
        # keys a query sees, by layer type: its document's earlier positions
        # and itself, inside the window on sliding layers
        seen = positions.astype(jnp.float32) + 1.0
        out.update(
            expert_tokens=jnp.stack(counts).astype(jnp.float32),
            pairs_dropped=dropped.astype(jnp.float32),
            buffer_rows=jnp.stack(buffer_rows).astype(jnp.float32),
            attn_keys_full=jnp.sum(seen),
            attn_keys_sliding=jnp.sum(jnp.minimum(seen, float(cfg.sliding_window))),
            n_sequences=jnp.asarray(b, jnp.float32),
            n_positions=jnp.asarray(b * t, jnp.float32),
        )
        return out
