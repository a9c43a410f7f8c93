"""Vision Transformer classifier — the model family that consumes ring attention.

Beyond-parity: the reference framework is CNN-only (SURVEY §5.7 — no attention op
anywhere), but this framework's long-context story (``parallel/ring_attention.py``)
needs a first-class consumer in the training stack, not a standalone demo. This is
a standard pre-LN ViT (Dosovitskiy et al., arXiv:2010.11929): patch-embed conv,
learned position embeddings, N transformer blocks, global-average-pool head —
trainable through the same SPMD train step and ``fit`` loop as the CNN classifiers
(``ClassificationTask``; no BatchNorm, so the batch_stats pytree is empty).

Sequence parallelism: with ``spatial_axis_name`` set, the input arrives H-sharded
(``shard_batch_spatial``), each shard patch-embeds its own rows into a contiguous
block of the row-major token sequence, attention runs as exact blockwise RING
attention over the sequence axis (K/V rotating one ppermute hop per step), and the
pooled head ``pmean``s across shards — so one chip never materializes the full
token sequence. MLPs and LayerNorms are token-local and need no communication.

TPU notes: matmul-dominated (QKV/proj/MLP ride the MXU), compute dtype follows
``ModelConfig.dtype`` with float32 params and float32 softmax accumulation,
``remat`` wraps each block in ``jax.checkpoint`` for activation memory.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from tensorflowdistributedlearning_tpu.config import ModelConfig
from tensorflowdistributedlearning_tpu.models.layers import scaled_width
from tensorflowdistributedlearning_tpu.parallel.pipeline import stack_stage_params
from tensorflowdistributedlearning_tpu.parallel.ring_attention import (
    attention_reference,
    ring_attention,
)


# compiled-Pallas gate for the fused-attention dispatch: an alias bound in
# THIS module's globals so tests can patch vit._fused_platform_ok without
# affecting the depthwise gate; both resolve to the one shared decision
# (ops/pallas_kernels.pallas_platform_ok)
from tensorflowdistributedlearning_tpu.models.layers import (  # noqa: E402
    _pallas_platform_ok as _fused_platform_ok,
)

# PATCH-token ceiling for the fused kernel. On a v5e chip, 2026-08-01, under
# the device-dominated protocol (bench_kernels._chained: kernels chained
# inside one program so device work, not dispatch, fills the window) the
# verdict at [32,T,6,64] is:
# train-step TIE at both T=196 and T=1024 (1.003x/1.005x), forward 0.97x at
# 196 and 1.14x at 1024. The gate sits at the measured ceiling — above it
# the kernel is unmeasured, and ops/flash_attention.py's own VMEM-budget
# fallback (_VMEM_KV_LIMIT_BYTES) already degrades oversized shapes to XLA.
# The ceiling counts PATCH tokens: this repo's ViT pools (no cls token), so
# its sequence length IS the patch count, and a variant that prepends
# auxiliary tokens (cls, registers) declares them via
# MultiHeadSelfAttention.num_prefix_tokens so a 1024-patch image does not
# fall back to XLA one token early (ADVICE round 5).
# After PR 26 this constant gates the ViT family's kernel only
# (ops/flash_attention.py); the decoder family's attention
# (ops/blocked_attention.py, chosen by shapes) has no such ceiling and is
# not routed here: it wants head sizes that are multiples of 128.
_FUSED_MAX_SEQ = 1024


class MultiHeadSelfAttention(nn.Module):
    """QKV projection + exact attention + output projection. ``spatial_axis_name``
    selects the ring formulation over the sequence mesh axis; both paths share the
    same float32-softmax math, so sharded and unsharded forwards agree to
    reassociation tolerance. ``use_fused`` swaps the XLA einsum path for the
    Pallas fused block-attention kernel (same contract, VMEM-resident scores) —
    on TPU only; elsewhere the flag degrades to the XLA path."""

    embed_dim: int
    num_heads: int
    spatial_axis_name: Optional[str] = None
    dtype: Optional[jnp.dtype] = None
    use_fused: bool = False
    # auxiliary tokens prepended to the patch sequence (cls token, register
    # tokens); excluded from the _FUSED_MAX_SEQ gate, whose ceiling was
    # measured in patch tokens. 0 for this repo's ViT (mean-pool head).
    num_prefix_tokens: int = 0

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, t, d = x.shape
        head_dim = self.embed_dim // self.num_heads
        qkv = nn.Dense(3 * self.embed_dim, dtype=self.dtype, name="qkv")(x)
        qkv = qkv.reshape(b, t, 3, self.num_heads, head_dim)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # [B, T, H, hd]
        if self.spatial_axis_name is not None:
            if self.use_fused:
                import warnings

                warnings.warn(
                    "use_fused_attention is ignored under sequence parallelism: "
                    "the ring formulation owns the attention math there",
                    stacklevel=2,
                )
            out = ring_attention(q, k, v, axis_name=self.spatial_axis_name)
        elif (
            self.use_fused
            and t - self.num_prefix_tokens <= _FUSED_MAX_SEQ
            and _fused_platform_ok()
        ):
            from tensorflowdistributedlearning_tpu.ops.flash_attention import (
                flash_attention,
            )

            out = flash_attention(q, k, v)
        else:
            # use_fused off-TPU degrades to the XLA path rather than the
            # Pallas interpreter (same platform gate as the depthwise
            # dispatch, models/layers.py), so presets can carry the flag
            # without slowing the CPU test mesh
            out = attention_reference(q, k, v)
        out = out.reshape(b, t, self.embed_dim)
        return nn.Dense(self.embed_dim, dtype=self.dtype, name="proj")(out)


class MoEMlp(nn.Module):
    """Switch-style top-1 mixture-of-experts FFN (arXiv:2101.03961) replacing a
    TransformerBlock's dense MLP.

    The router (float32, like the softmax accumulations elsewhere) picks one
    expert per token under a per-expert capacity; dropped tokens contribute a
    zero update (the residual carries them through). Training adds the
    load-balancing auxiliary loss, sown into the ``aux_loss`` collection —
    the train steps add every sown value to the objective; without it, top-1
    routing + capacity drops collapse onto few experts. Dispatch fractions are
    also sown into ``intermediates`` for utilization monitoring.

    ``expert_axis_name=None`` computes every expert locally
    (``dense_moe_apply`` — trainable on any mesh); with an axis name set, THIS
    shard's expert slice runs under the ``moe_apply`` all-to-all (one expert
    per shard on the mesh axis), with identical numerics — the final pmean
    clears the axis-varying type (every shard reconstructs the same combined
    tokens because the token batch is replicated across the expert axis)."""

    embed_dim: int
    mlp_dim: int
    n_experts: int
    capacity_factor: float = 1.25
    aux_weight: float = 0.01
    expert_axis_name: Optional[str] = None
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        from tensorflowdistributedlearning_tpu.parallel.expert import (
            dense_moe_apply,
            load_balance_loss,
            moe_apply,
        )

        b, t, d = x.shape
        tokens = x.reshape(b * t, d)
        router = self.param(
            "router",
            nn.initializers.normal(stddev=0.02),
            (d, self.n_experts),
            jnp.float32,
        )
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        w_in = self.param(
            "w_in", init, (self.n_experts, d, self.mlp_dim), jnp.float32
        )
        b_in = self.param(
            "b_in", nn.initializers.zeros, (self.n_experts, self.mlp_dim), jnp.float32
        )
        w_out = self.param(
            "w_out", init, (self.n_experts, self.mlp_dim, d), jnp.float32
        )
        b_out = self.param(
            "b_out", nn.initializers.zeros, (self.n_experts, d), jnp.float32
        )

        # ONE float32 routing, shared by the aux-loss statistics AND the
        # dispatch below (passing gate_logits through keeps near-tie argmax
        # decisions identical between what the balance loss optimizes and
        # where tokens actually go, regardless of compute dtype)
        gate_logits = tokens.astype(jnp.float32) @ router
        if not self.is_initializing():  # init would bake stale sown values
            self.sow(
                "aux_loss",
                "load_balance",
                self.aux_weight * load_balance_loss(gate_logits),
            )
            chosen = jnp.argmax(gate_logits, axis=-1)
            fractions = jnp.mean(
                jax.nn.one_hot(chosen, self.n_experts, dtype=jnp.float32), axis=0
            )
            self.sow("intermediates", "expert_fraction", fractions)

        dtype = self.dtype or jnp.float32
        stacked = {
            "w_in": w_in.astype(dtype),
            "b_in": b_in.astype(dtype),
            "w_out": w_out.astype(dtype),
            "b_out": b_out.astype(dtype),
        }

        def expert_fn(p, xs):
            h = xs @ p["w_in"] + p["b_in"]
            h = nn.gelu(h)
            return h @ p["w_out"] + p["b_out"]

        tokens_c = tokens.astype(dtype)
        if self.expert_axis_name is None:
            out = dense_moe_apply(
                expert_fn,
                stacked,
                router,
                tokens_c,
                capacity_factor=self.capacity_factor,
                gate_logits=gate_logits,
            )
        else:
            idx = lax.axis_index(self.expert_axis_name)
            mine = jax.tree.map(
                lambda p: lax.dynamic_index_in_dim(p, idx, 0, keepdims=False),
                stacked,
            )
            out = moe_apply(
                expert_fn,
                mine,
                router,
                tokens_c,
                capacity_factor=self.capacity_factor,
                axis_name=self.expert_axis_name,
                gate_logits=gate_logits,
            )
            # every shard combines the same tokens (batch replicated across
            # the expert axis): numerically an identity, clears the varying type
            out = lax.pmean(out, self.expert_axis_name)
        return out.reshape(b, t, d)


class TransformerBlock(nn.Module):
    """Pre-LN block: x + MHSA(LN(x)); x + MLP(LN(x)). With ``moe_experts`` set,
    the MLP is the Switch-style ``MoEMlp`` instead of the dense pair."""

    embed_dim: int
    num_heads: int
    mlp_dim: int
    spatial_axis_name: Optional[str] = None
    dtype: Optional[jnp.dtype] = None
    use_fused: bool = False
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    expert_axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        h = nn.LayerNorm(dtype=self.dtype, name="ln1")(x)
        x = x + MultiHeadSelfAttention(
            self.embed_dim,
            self.num_heads,
            spatial_axis_name=self.spatial_axis_name,
            dtype=self.dtype,
            use_fused=self.use_fused,
            name="attn",
        )(h)
        h = nn.LayerNorm(dtype=self.dtype, name="ln2")(x)
        if self.moe_experts:
            return x + MoEMlp(
                self.embed_dim,
                self.mlp_dim,
                self.moe_experts,
                capacity_factor=self.moe_capacity_factor,
                aux_weight=self.moe_aux_weight,
                expert_axis_name=self.expert_axis_name,
                dtype=self.dtype,
                name="moe",
            )(h)
        h = nn.Dense(self.mlp_dim, dtype=self.dtype, name="mlp_in")(h)
        h = nn.gelu(h)
        h = nn.Dense(self.embed_dim, dtype=self.dtype, name="mlp_out")(h)
        return x + h


class ViTClassifier(nn.Module):
    """ViT classification network: [B, H, W, C] -> [B, num_classes] float32 logits.

    Under ``spatial_axis_name`` the input is the device's H-shard; its patches form
    tokens ``[axis_index * T_local, (axis_index + 1) * T_local)`` of the row-major
    global sequence (matching ring attention's block-order convention), and the
    position-embedding table is sliced accordingly."""

    config: ModelConfig
    bn_axis_name: Optional[str] = None  # accepted for factory symmetry; ViT has no BN
    spatial_axis_name: Optional[str] = None
    # expert-parallel execution for the MoE blocks (config.moe_experts > 0):
    # one expert per shard on this mesh axis, all-to-all dispatch; None runs
    # every expert locally (trainable on any mesh)
    expert_axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        cfg = self.config
        if cfg.num_classes is None:
            raise ValueError(
                "backbone='vit' supports the classification head only "
                "(set num_classes)"
            )
        p = cfg.patch_size
        embed = scaled_width(cfg.embed_dim, cfg.width_multiplier)
        if embed % cfg.num_heads != 0:
            raise ValueError(
                f"scaled embed_dim {embed} not divisible by num_heads "
                f"{cfg.num_heads}"
            )
        h_total, w_total = cfg.input_shape
        if h_total % p or w_total % p:
            raise ValueError(
                f"input_shape {cfg.input_shape} not divisible by patch_size {p}"
            )
        # Validate the ACTUAL input against the configured geometry: the position
        # table is laid out row-major for input_shape's patch grid, so a
        # different-sized input would silently index wrong embeddings.
        h_local, w_actual = x.shape[1], x.shape[2]
        if w_actual != w_total:
            raise ValueError(
                f"input width {w_actual} != configured input_shape width {w_total}"
            )
        if self.spatial_axis_name is not None:
            degree = lax.axis_size(self.spatial_axis_name)
            if h_local * degree != h_total:
                raise ValueError(
                    f"per-shard height {h_local} x sequence degree {degree} != "
                    f"configured input height {h_total}"
                )
        elif h_local != h_total:
            raise ValueError(
                f"input height {h_local} != configured input_shape height {h_total}"
            )
        if h_local % p:
            raise ValueError(
                f"per-shard height {h_local} not divisible by patch_size {p} — "
                "lower sequence_parallel or the patch size"
            )
        dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        x = x.astype(dtype)

        tokens = nn.Conv(
            embed,
            (p, p),
            strides=(p, p),
            padding="VALID",
            dtype=dtype,
            name="patch_embed",
        )(x)
        b = tokens.shape[0]
        t_local = tokens.shape[1] * tokens.shape[2]
        tokens = tokens.reshape(b, t_local, embed)

        t_global = (h_total // p) * (w_total // p)
        pos = self.param(
            "pos_embedding",
            nn.initializers.normal(stddev=0.02),
            (t_global, embed),
            jnp.float32,
        )
        if self.spatial_axis_name is not None:
            offset = lax.axis_index(self.spatial_axis_name) * t_local
            pos_local = lax.dynamic_slice_in_dim(pos, offset, t_local, axis=0)
        else:
            pos_local = pos[:t_local]
        tokens = tokens + pos_local.astype(dtype)[None]

        block_cls = TransformerBlock
        if cfg.remat:
            block_cls = nn.remat(block_cls, static_argnums=(2,))
        mlp_dim = int(embed * cfg.mlp_ratio)
        for i in range(cfg.vit_layers):
            # Switch-style placement: every OTHER block's FFN is a top-1 MoE
            # (block2, block4, ... — arXiv:2101.03961 alternates too); the
            # interleaved dense blocks stabilize training
            is_moe = cfg.moe_experts > 0 and i % 2 == 1
            tokens = block_cls(
                embed,
                cfg.num_heads,
                mlp_dim,
                spatial_axis_name=self.spatial_axis_name,
                dtype=dtype,
                use_fused=cfg.use_fused_attention,
                moe_experts=cfg.moe_experts if is_moe else 0,
                moe_capacity_factor=cfg.moe_capacity_factor,
                moe_aux_weight=cfg.moe_aux_weight,
                expert_axis_name=self.expert_axis_name if is_moe else None,
                name=f"block{i + 1}",
            )(tokens, train)

        tokens = nn.LayerNorm(dtype=dtype, name="ln_final")(tokens)
        pooled = jnp.mean(tokens.astype(jnp.float32), axis=1)
        if self.spatial_axis_name is not None:
            # equal-sized shards: the global token mean is the pmean of locals
            pooled = lax.pmean(pooled, self.spatial_axis_name)
        return nn.Dense(cfg.num_classes, name="logits")(pooled)


def pipeline_stage_fn(config: ModelConfig):
    """Stage function for GPipe pipeline parallelism over ViT blocks
    (parallel/pipeline.py): applies ONE TransformerBlock given its param tree.

    Takes the ``ModelConfig`` and derives embed width, MLP width, and compute
    dtype exactly as ``ViTClassifier.__call__`` does, so the pipelined blocks
    are numerically identical to the trained model's (a hand-passed dtype or
    width mismatch would diverge silently — params are float32 either way).

    ViT's repeated blocks are exactly the homogeneous-stage regime the pipeline
    runner targets (identical computation + param shapes per layer); pair with
    ``stack_vit_block_params`` to turn a trained ViT's variables into the
    stacked [K, ...] stage params the runner shards over the model axis."""
    embed = scaled_width(config.embed_dim, config.width_multiplier)
    dtype = jnp.bfloat16 if config.dtype == "bfloat16" else jnp.float32
    block = TransformerBlock(
        embed,
        config.num_heads,
        int(embed * config.mlp_ratio),
        dtype=dtype,
        use_fused=config.use_fused_attention,
    )

    def stage_fn(params, x):
        return block.apply({"params": params}, x, False)

    return stage_fn


def grouped_pipeline_stage_fn(config: ModelConfig, layers_per_stage: int):
    """Stage function over the GROUPED stacking [layers_per_stage, ...] —
    always expects the group axis, even when it is 1 (the form
    ``stack_vit_block_params(..., n_stages=K)`` produces per stage). Used by
    train/pipeline_step.py so stage params slice uniformly."""
    base = pipeline_stage_fn(config)

    def stage_fn(params, x):
        for i in range(layers_per_stage):
            x = base(jax.tree.map(lambda p, i=i: p[i], params), x)
        return x

    return stage_fn


def stack_vit_block_params(params, n_layers: int, n_stages: Optional[int] = None):
    """Stack a ViTClassifier's per-layer block params for the pipeline runner;
    layers must exist as ``block1..blockN``.

    ``n_stages=None``: [L, ...] leading stage axis (one layer per stage).
    ``n_stages=K``: grouped form [K, L/K, ...] — consecutive layers share a
    stage, matching ``pipeline_stage_fn(config, layers_per_stage=L//K)``."""
    stacked = stack_stage_params(
        [params[f"block{i + 1}"] for i in range(n_layers)]
    )
    if n_stages is None:
        return stacked
    if n_layers % n_stages:
        raise ValueError(
            f"{n_layers} ViT layers not divisible into {n_stages} pipeline stages"
        )
    group = n_layers // n_stages
    return jax.tree.map(
        lambda leaf: leaf.reshape((n_stages, group) + leaf.shape[1:]), stacked
    )


def embed_tokens(config: ModelConfig, params, x: jax.Array) -> jax.Array:
    """Patch-embed + position embeddings outside the module — the pre-block
    half of ``ViTClassifier.__call__`` (unsharded layout), applied from a
    trained model's param tree. Used by the pipeline-parallel train step, which
    runs the blocks through the GPipe runner instead of the module loop."""
    embed = scaled_width(config.embed_dim, config.width_multiplier)
    dtype = jnp.bfloat16 if config.dtype == "bfloat16" else jnp.float32
    p = config.patch_size
    x = x.astype(dtype)
    conv = nn.Conv(
        embed, (p, p), strides=(p, p), padding="VALID", dtype=dtype
    )
    tokens = conv.apply({"params": params["patch_embed"]}, x)
    b = tokens.shape[0]
    tokens = tokens.reshape(b, -1, embed)
    pos = params["pos_embedding"][: tokens.shape[1]]
    return tokens + pos.astype(dtype)[None]


def head_logits(config: ModelConfig, params, tokens: jax.Array) -> jax.Array:
    """Final LayerNorm + mean-pool + logits head — the post-block half of
    ``ViTClassifier.__call__`` (unsharded layout), for the pipeline step."""
    dtype = jnp.bfloat16 if config.dtype == "bfloat16" else jnp.float32
    tokens = nn.LayerNorm(dtype=dtype).apply({"params": params["ln_final"]}, tokens)
    pooled = jnp.mean(tokens.astype(jnp.float32), axis=1)
    return nn.Dense(config.num_classes).apply({"params": params["logits"]}, pooled)
