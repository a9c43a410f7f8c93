"""ResNet-v2 "beta" backbone with DeepLabV3+-style segmentation head and a
classification head, as Flax modules (reference: core/resnet.py).

Re-design notes (TPU-first, not a translation):

- The reference threaded slim arg_scopes and a TF collection of end_points through the
  graph (core/resnet.py:225-257); here blocks are explicit modules and the backbone
  returns an end-point dict.
- The reference computed strided units as full-resolution conv followed by subsampling
  (core/resnet.py:85-87, 139-141); here the stride is fused into the conv — the same
  function family at 1/stride^2 of the FLOPs, which matters on the MXU.
- slim's atrous bookkeeping (``stack_blocks_dense`` with ``output_stride``, reference:
  core/resnet.py:244) is reproduced as a static Python loop: once the target stride is
  reached, further strides convert to accumulating dilation rates.
- The reference's ``block2`` used base_depth=258 — a typo for 256 that breaks
  power-of-two channel sizes (SURVEY §2.4.6); 256 is used here. Its ``output_stride /= 4``
  outside the None-guard (core/resnet.py:239, TypeError when None) is fixed by treating
  None as "no atrous" (standard stride-32 net, used by the classification path).
- The decoder upsampled ASPP output to a hard-coded (26, 26) and looked up the skip
  tensor by a scope-name string (core/resnet.py:474-480); here the skip's actual spatial
  shape is used, so any input size works.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tensorflowdistributedlearning_tpu.config import ModelConfig
from tensorflowdistributedlearning_tpu.models.layers import (
    scaled_width,
    ConvBN,
    SplitSeparableConv2D,
    conv_kernel_init,
    subsample,
    upsample,
)
from tensorflowdistributedlearning_tpu.obs import scopes

# Reference: core/resnet.py:14 (_DEFAULT_MULTI_GRID = [2, 2, 2]); resnet_model passes
# (1, 2, 1) for the segmentation net (core/resnet.py:435).
DEFAULT_MULTI_GRID = (2, 2, 2)
SEGMENTATION_MULTI_GRID = (1, 2, 1)


@dataclasses.dataclass(frozen=True)
class UnitSpec:
    depth: int
    depth_bottleneck: int
    stride: int
    unit_rate: int = 1


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    name: str
    units: Tuple[UnitSpec, ...]


def resnet_block_specs(
    n_blocks: Tuple[int, ...],
    multi_grid: Tuple[int, int, int] = SEGMENTATION_MULTI_GRID,
    width_multiplier: float = 1.0,
) -> Tuple[BlockSpec, ...]:
    """Block layout of the reference's ``resnet_v2`` (core/resnet.py:330-344):
    three stages with the stride-2 unit LAST (v2-beta convention), then an atrous
    multi-grid stage of three units (depth 1024 / bottleneck 256 / stride 1).
    All widths scale by ``width_multiplier`` (1.0 = reference widths).
    """
    if len(n_blocks) != 3:
        raise ValueError("Expect n_blocks to have length 3.")
    if len(multi_grid) != 3:
        raise ValueError("Expect multi_grid to have length 3.")

    def w(c: int) -> int:
        return scaled_width(c, width_multiplier)

    def stage(name: str, base_depth: int, num_units: int) -> BlockSpec:
        units = tuple(
            UnitSpec(depth=w(base_depth * 4), depth_bottleneck=w(base_depth), stride=1)
            for _ in range(num_units - 1)
        ) + (
            UnitSpec(depth=w(base_depth * 4), depth_bottleneck=w(base_depth), stride=2),
        )
        return BlockSpec(name, units)

    block4 = BlockSpec(
        "block4",
        tuple(
            UnitSpec(depth=w(1024), depth_bottleneck=w(256), stride=1, unit_rate=r)
            for r in multi_grid
        ),
    )
    return (
        stage("block1", 128, n_blocks[0]),
        stage("block2", 256, n_blocks[1]),  # reference had 258, a typo (SURVEY §2.4.6)
        stage("block3", 512, n_blocks[2]),
        block4,
    )


def classic_block_specs(
    n_blocks: Tuple[int, ...],
    width_multiplier: float = 1.0,
) -> Tuple[BlockSpec, ...]:
    """Standard ResNet-50/101/152 stage ladder: four stages at bottleneck widths
    64/128/256/512 (outputs 256/512/1024/2048), stride-2 unit LAST per the
    family's v2-beta convention, final stage unstrided — overall stride 32 with
    the root's 4. This is the published architecture ImageNet numbers quote
    (``n_blocks=(3, 4, 6, 3)`` = ResNet-50); the reference's own layout
    (``resnet_block_specs``) runs ~3x these FLOPs (doubled widths + the
    1024-wide atrous stage, reference: core/resnet.py:330-344)."""
    if len(n_blocks) != 4:
        raise ValueError("classic layout expects n_blocks of length 4, e.g. (3, 4, 6, 3)")

    def w(c: int) -> int:
        return scaled_width(c, width_multiplier)

    specs = []
    for name, base, num_units, last_stride in zip(
        ("block1", "block2", "block3", "block4"),
        (64, 128, 256, 512),
        n_blocks,
        (2, 2, 2, 1),
    ):
        units = tuple(
            UnitSpec(depth=w(base * 4), depth_bottleneck=w(base), stride=1)
            for _ in range(num_units - 1)
        ) + (
            UnitSpec(depth=w(base * 4), depth_bottleneck=w(base), stride=last_stride),
        )
        specs.append(BlockSpec(name, units))
    return tuple(specs)


class BottleneckUnit(nn.Module):
    """Pre-activation bottleneck residual unit (reference: core/resnet.py:94-152).

    preact BN+relu -> 1x1 reduce (BN+relu) -> 3x3 atrous (BN+relu, stride fused) ->
    1x1 expand (plain, bias) ; shortcut = identity subsample or plain 1x1 conv of the
    preactivation; output = relu(shortcut + residual).

    Returns (output, residual) — the residual branch pre-addition is what the decoder
    taps as its skip (reference: core/resnet.py:476-480 fetched the conv3 end point).
    """

    spec: UnitSpec
    rate: int = 1
    bn_decay: float = 0.99
    bn_epsilon: float = 0.001
    bn_scale: bool = True
    bn_axis_name: Optional[str] = None
    spatial_axis_name: Optional[str] = None
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False):
        spec = self.spec
        depth_in = x.shape[-1]
        preact = nn.relu(
            nn.BatchNorm(
                use_running_average=not train,
                momentum=self.bn_decay,
                epsilon=self.bn_epsilon,
                use_scale=self.bn_scale,
                axis_name=self.bn_axis_name,
                dtype=self.dtype,
                name="preact",
            )(x)
        )
        if spec.depth == depth_in:
            shortcut = subsample(x, spec.stride)
        else:
            shortcut = nn.Conv(
                spec.depth,
                (1, 1),
                strides=(spec.stride, spec.stride),
                kernel_init=conv_kernel_init,
                dtype=self.dtype,
                name="shortcut",
            )(preact)
        common = dict(
            bn_decay=self.bn_decay,
            bn_epsilon=self.bn_epsilon,
            bn_scale=self.bn_scale,
            bn_axis_name=self.bn_axis_name,
            spatial_axis_name=self.spatial_axis_name,
            dtype=self.dtype,
        )
        residual = ConvBN(spec.depth_bottleneck, 1, 1, name="conv1", **common)(
            preact, train
        )
        residual = ConvBN(
            spec.depth_bottleneck,
            3,
            stride=spec.stride,
            rate=self.rate * spec.unit_rate,
            name="conv2",
            **common,
        )(residual, train)
        residual = nn.Conv(
            spec.depth,
            (1, 1),
            kernel_init=conv_kernel_init,
            dtype=self.dtype,
            name="conv3",
        )(residual)
        return nn.relu(shortcut + residual), residual


class BasicBlockUnit(nn.Module):
    """Pre-activation basic (two-conv) residual unit (reference: core/resnet.py:57-91).
    Output width is ``depth_bottleneck`` — the reference's basic block ignored ``depth``
    for the residual path and shortcut alike."""

    spec: UnitSpec
    rate: int = 1
    bn_decay: float = 0.99
    bn_epsilon: float = 0.001
    bn_scale: bool = True
    bn_axis_name: Optional[str] = None
    spatial_axis_name: Optional[str] = None
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False):
        spec = self.spec
        depth_in = x.shape[-1]
        preact = nn.relu(
            nn.BatchNorm(
                use_running_average=not train,
                momentum=self.bn_decay,
                epsilon=self.bn_epsilon,
                use_scale=self.bn_scale,
                axis_name=self.bn_axis_name,
                dtype=self.dtype,
                name="preact",
            )(x)
        )
        if spec.depth_bottleneck == depth_in:
            shortcut = subsample(x, spec.stride)
        else:
            shortcut = nn.Conv(
                spec.depth_bottleneck,
                (1, 1),
                strides=(spec.stride, spec.stride),
                kernel_init=conv_kernel_init,
                dtype=self.dtype,
                name="shortcut",
            )(preact)
        residual = ConvBN(
            spec.depth_bottleneck,
            3,
            stride=spec.stride,
            bn_decay=self.bn_decay,
            bn_epsilon=self.bn_epsilon,
            bn_scale=self.bn_scale,
            bn_axis_name=self.bn_axis_name,
            spatial_axis_name=self.spatial_axis_name,
            dtype=self.dtype,
            name="conv1",
        )(preact, train)
        if self.spatial_axis_name is not None:
            from tensorflowdistributedlearning_tpu.models.layers import SpatialConv

            residual = SpatialConv(
                spec.depth_bottleneck,
                3,
                rate=self.rate * spec.unit_rate,
                axis_name=self.spatial_axis_name,
                dtype=self.dtype,
                name="conv2",
            )(residual)
        else:
            residual = nn.Conv(
                spec.depth_bottleneck,
                (3, 3),
                kernel_dilation=(self.rate * spec.unit_rate,) * 2,
                padding="SAME",
                kernel_init=conv_kernel_init,
                dtype=self.dtype,
                name="conv2",
            )(residual)
        return nn.relu(shortcut + residual), residual


class ResNetBackbone(nn.Module):
    """ResNet-v2-beta feature extractor (reference: core/resnet.py:171-257).

    Root: three 3x3 convs (64/64/128, first stride 2) replacing the classic 7x7
    (reference: core/resnet.py:155-168), SAME max-pool, post-norm BN+relu; then the four
    residual stages with atrous output_stride control. Returns an end-point dict with
    'root', each 'block{i}', 'block1_unit1_residual' (decoder skip), and 'features'.
    """

    config: ModelConfig
    multi_grid: Tuple[int, int, int] = SEGMENTATION_MULTI_GRID
    bn_axis_name: Optional[str] = None
    spatial_axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> Dict[str, jax.Array]:
        cfg = self.config
        dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        x = x.astype(dtype)
        common = dict(
            bn_decay=cfg.batch_norm_decay,
            bn_epsilon=cfg.batch_norm_epsilon,
            bn_scale=cfg.batch_norm_scale,
            bn_axis_name=self.bn_axis_name,
            spatial_axis_name=self.spatial_axis_name,
            dtype=dtype,
        )

        output_stride = cfg.output_stride
        if output_stride is not None:
            if output_stride % 4 != 0:
                raise ValueError("The output_stride needs to be a multiple of 4.")
            # the root block already strides by 4 (reference: core/resnet.py:236-239,
            # with the /=4-outside-the-guard defect fixed)
            target_stride = output_stride // 4
        else:
            target_stride = None

        end_points: Dict[str, jax.Array] = {}
        wm = cfg.width_multiplier
        # root (reference: core/resnet.py:155-168, 241-242)
        x = ConvBN(
            scaled_width(64, wm),
            3,
            stride=2,
            space_to_depth=cfg.stem_space_to_depth,
            name="conv1_1",
            **common,
        )(x, train)
        x = ConvBN(scaled_width(64, wm), 3, name="conv1_2", **common)(x, train)
        x = ConvBN(scaled_width(128, wm), 3, name="conv1_3", **common)(x, train)
        if self.spatial_axis_name is not None:
            from tensorflowdistributedlearning_tpu.parallel.spatial import (
                spatial_max_pool,
            )

            x = spatial_max_pool(
                x, window=3, stride=2, axis_name=self.spatial_axis_name
            )
        else:
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        x = nn.relu(
            nn.BatchNorm(
                use_running_average=not train,
                momentum=cfg.batch_norm_decay,
                epsilon=cfg.batch_norm_epsilon,
                use_scale=cfg.batch_norm_scale,
                axis_name=self.bn_axis_name,
                dtype=dtype,
                name="postnorm",
            )(x)
        )
        end_points["root"] = x

        unit_cls = BasicBlockUnit if cfg.block_type == "basic_block" else BottleneckUnit
        if cfg.remat:
            # rematerialize each residual unit on the backward pass: activations are
            # recomputed instead of stored, trading MXU FLOPs for HBM — the knob the
            # large-batch pod configs rely on (a TPU-first capability; the reference
            # had no memory-saving story). `train` is static (BN mode selection).
            unit_cls = nn.remat(unit_cls, static_argnums=(2,))
        if cfg.block_layout == "classic":
            blocks = classic_block_specs(cfg.n_blocks, wm)
        else:
            blocks = resnet_block_specs(cfg.n_blocks, self.multi_grid, wm)

        # slim stack_blocks_dense semantics (reference: core/resnet.py:244): strides
        # apply until the target stride is hit, after which they accumulate into rates.
        current_stride = 1
        rate = 1
        for block in blocks:
            for i, unit in enumerate(block.units):
                if target_stride is not None and current_stride == target_stride:
                    applied = dataclasses.replace(unit, stride=1)
                    unit_rate_accum = rate
                    rate *= unit.stride
                else:
                    applied = unit
                    unit_rate_accum = 1
                    current_stride *= unit.stride
                x, residual = unit_cls(
                    spec=applied,
                    rate=unit_rate_accum,
                    name=f"{block.name}_unit{i + 1}",
                    **common,
                )(x, train)
                if block.name == "block1" and i == 0:
                    end_points["block1_unit1_residual"] = residual
            end_points[block.name] = x
        if target_stride is not None and current_stride != target_stride:
            raise ValueError("output_stride is unreachable with this block layout.")
        end_points["features"] = x
        return end_points


class ASPP(nn.Module):
    """Atrous spatial pyramid pooling head (reference: core/resnet.py:440-472):
    1x1 conv, three split-separable atrous convs at rates 2/4/8, and a global-pool
    branch upsampled back, concatenated and fused by a 1x1 conv."""

    config: ModelConfig
    bn_axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        cfg = self.config
        dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        common = dict(
            bn_decay=cfg.batch_norm_decay,
            bn_epsilon=cfg.batch_norm_epsilon,
            bn_scale=cfg.batch_norm_scale,
            bn_axis_name=self.bn_axis_name,
            dtype=dtype,
        )
        depth = cfg.base_depth
        out_size = x.shape[1:3]
        sep = dict(common, use_pallas=cfg.use_pallas_depthwise)
        a1 = ConvBN(depth, 1, name="conv_1x1", **common)(x, train)
        a2 = SplitSeparableConv2D(depth, 3, rate=2, name="conv_3x3_1", **sep)(x, train)
        a3 = SplitSeparableConv2D(depth, 3, rate=4, name="conv_3x3_2", **sep)(x, train)
        a4 = SplitSeparableConv2D(depth, 3, rate=8, name="conv_3x3_3", **sep)(x, train)
        pooled = jnp.mean(x, axis=(1, 2), keepdims=True)
        pooled = ConvBN(depth, 1, name="pool_conv_1x1", **common)(pooled, train)
        a5 = upsample(pooled, out_size).astype(dtype)
        cat = jnp.concatenate([a1, a2, a3, a4, a5], axis=-1)
        return ConvBN(depth, 1, name="project", **common)(cat, train)


def deeplab_head(
    cfg: ModelConfig,
    bn_axis_name: Optional[str],
    features: jax.Array,
    skip: jax.Array,
    train: bool,
) -> jax.Array:
    """Shared DeepLabV3+ head: ASPP over the backbone features, upsample to the
    skip resolution, 1x1-projected skip concat, 3x3 fuse to one channel, bilinear
    upsample to input resolution in float32 (reference: core/resnet.py:440-496 —
    with the hard-coded (26, 26) generalized to the skip tensor's actual shape,
    SURVEY §2.4.7). MUST be called inside a module's compact ``__call__`` so the
    submodules bind to that module's parameter scope; both segmentation networks
    (ResNet, Xception) use it, keeping their heads structurally identical.
    """
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    common = dict(
        bn_decay=cfg.batch_norm_decay,
        bn_epsilon=cfg.batch_norm_epsilon,
        bn_scale=cfg.batch_norm_scale,
        bn_axis_name=bn_axis_name,
        dtype=dtype,
    )
    with scopes.scope("seg/aspp"):
        aspp = ASPP(cfg, bn_axis_name=bn_axis_name, name="aspp")(features, train)
    with scopes.scope("seg/decoder"):
        aspp_up = upsample(aspp, skip.shape[1:3]).astype(dtype)
        decoder = ConvBN(cfg.base_depth, 1, name="decoder_conv_1x1", **common)(skip, train)
        decoder = jnp.concatenate([decoder, aspp_up], axis=-1)
        decoder = nn.Conv(
            1,
            (3, 3),
            padding="SAME",
            kernel_init=conv_kernel_init,
            dtype=dtype,
            name="decoder_conv_3x3",
        )(decoder)
        return upsample(decoder.astype(jnp.float32), cfg.input_shape)


class ResNetSegmentation(nn.Module):
    """Full segmentation network: backbone + ASPP + decoder with block1 skip, producing
    per-pixel logits at input resolution (reference: core/resnet.py:398-496). Logits are
    returned in float32 regardless of compute dtype."""

    config: ModelConfig
    bn_axis_name: Optional[str] = None
    spatial_axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        cfg = self.config
        with scopes.scope("seg/backbone"):
            end_points = ResNetBackbone(
                cfg, multi_grid=SEGMENTATION_MULTI_GRID, bn_axis_name=self.bn_axis_name,
                spatial_axis_name=self.spatial_axis_name,
                name="backbone",
            )(x, train)
        features = end_points["features"]
        skip = end_points["block1_unit1_residual"]
        if self.spatial_axis_name is not None:
            # the backbone (where the FLOPs live) ran H-sharded; the head's bilinear
            # upsamplings and the per-image loss need whole maps, so reassemble here
            # (one all-gather per tensor over the sequence axis)
            from tensorflowdistributedlearning_tpu.parallel.spatial import (
                spatial_gather,
            )

            features = spatial_gather(features, axis_name=self.spatial_axis_name)
            skip = spatial_gather(skip, axis_name=self.spatial_axis_name)
        return deeplab_head(cfg, self.bn_axis_name, features, skip, train)


class ResNetClassifier(nn.Module):
    """Classification path (reference: core/resnet.py:246-256 kept global_pool +
    num_classes logits alongside the dense path). Uses output_stride=None semantics —
    all strides applied, overall stride 32. Returns [B, num_classes] float32 logits."""

    config: ModelConfig
    bn_axis_name: Optional[str] = None
    spatial_axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        cfg = self.config
        if cfg.num_classes is None:
            raise ValueError("ResNetClassifier requires config.num_classes")
        backbone_cfg = dataclasses.replace(cfg, output_stride=None)
        end_points = ResNetBackbone(
            backbone_cfg,
            multi_grid=DEFAULT_MULTI_GRID,
            bn_axis_name=self.bn_axis_name,
            spatial_axis_name=self.spatial_axis_name,
            name="backbone",
        )(x, train)
        if self.spatial_axis_name is not None:
            from tensorflowdistributedlearning_tpu.parallel.spatial import (
                spatial_global_mean,
            )

            pooled = spatial_global_mean(
                end_points["features"], axis_name=self.spatial_axis_name
            )
        else:
            pooled = jnp.mean(end_points["features"], axis=(1, 2))
        logits = nn.Dense(
            cfg.num_classes,
            kernel_init=conv_kernel_init,
            name="logits",
        )(pooled.astype(jnp.float32))
        return logits


def sample_input(config: ModelConfig, full: bool = False) -> np.ndarray:
    """Zeros shaped like one example of the model's input: what the trainers
    initialise on and the planner traces. Images for the image backbones;
    for the decoder a row of token ids — a few tokens to initialise on
    (nothing of the parameters depends on the length), the training sequence
    length with ``full``."""
    if config.decoder is not None:
        from tensorflowdistributedlearning_tpu.models.decoder import INIT_TOKENS

        length = config.decoder.sequence_length if full else INIT_TOKENS
        return np.zeros((1, length), np.int32)
    return np.zeros((1, *config.input_shape, config.input_channels), np.float32)


def build_model(
    config: ModelConfig,
    bn_axis_name: Optional[str] = None,
    spatial_axis_name: Optional[str] = None,
    expert_axis_name: Optional[str] = None,
) -> nn.Module:
    """Factory selecting backbone family and head from the config (the reference chose
    via ``resnet_model(...)`` arguments, model.py:356-370; Xception existed but was dead
    code — here it is a working first-class citizen).

    ``spatial_axis_name`` builds the model for H-sharded sequence-parallel
    execution inside ``shard_map`` (parallel/spatial.py); pair it with
    ``bn_axis_name`` on the same axis so BN statistics span the full spatial
    extent. Supported by both backbone families. ``expert_axis_name`` (ViT with
    ``moe_experts`` only) runs the MoE blocks expert-parallel: one expert per
    shard on that mesh axis with all-to-all dispatch (parallel/expert.py).

    Memoized: flax modules are immutable, and returning the SAME instance for the
    same arguments makes ``model.apply``/``model.init`` compare equal as jit
    statics, so compiled executables are shared across folds, Trainer instances,
    and tests (bound methods of two equal-but-distinct modules do NOT compare
    equal). The public wrapper normalizes positional/keyword call styles so every
    spelling shares one cache entry."""
    return _build_model_cached(
        config, bn_axis_name, spatial_axis_name, expert_axis_name
    )


@functools.lru_cache(maxsize=None)
def _build_model_cached(
    config: ModelConfig,
    bn_axis_name: Optional[str],
    spatial_axis_name: Optional[str],
    expert_axis_name: Optional[str],
) -> nn.Module:
    if config.backbone == "decoder":
        if bn_axis_name or spatial_axis_name or expert_axis_name:
            raise ValueError(
                "backbone='decoder' takes no mesh axis: its share of a layer is "
                "in its configuration (config.py:DecoderConfig)"
            )
        from tensorflowdistributedlearning_tpu.models.decoder import MoEDecoder

        return MoEDecoder(config)
    if config.backbone == "vit":
        from tensorflowdistributedlearning_tpu.models.vit import ViTClassifier

        return ViTClassifier(
            config,
            bn_axis_name=bn_axis_name,
            spatial_axis_name=spatial_axis_name,
            expert_axis_name=expert_axis_name,
        )
    if expert_axis_name is not None:
        raise ValueError(
            "expert_axis_name applies to backbone='vit' MoE models only"
        )
    if config.backbone == "resnet":
        if config.num_classes is None:
            return ResNetSegmentation(
                config,
                bn_axis_name=bn_axis_name,
                spatial_axis_name=spatial_axis_name,
            )
        return ResNetClassifier(
            config,
            bn_axis_name=bn_axis_name,
            spatial_axis_name=spatial_axis_name,
        )
    from tensorflowdistributedlearning_tpu.models.xception import (
        Xception41,
        XceptionSegmentation,
    )

    if config.num_classes is None:
        return XceptionSegmentation(
            config, bn_axis_name=bn_axis_name, spatial_axis_name=spatial_axis_name
        )
    return Xception41(
        config, bn_axis_name=bn_axis_name, spatial_axis_name=spatial_axis_name
    )
