"""Typed configuration for the framework.

The reference exposed its knobs as module constants plus ad-hoc ``**kwargs`` plumbing in
``Model.__init__`` (reference: model.py:13-24, 63-106). Here the same knob set is a pair of
frozen dataclasses so configs are explicit, hashable (usable as jit static args), and
serializable. The reference's ``batch_norm_decay`` copy-paste bug (it read
``kwargs["weight_decay"]``, reference: model.py:69) is intentionally NOT reproduced.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


DECODER_LAYER_TYPES = ("sliding_attention", "full_attention", "sparse_attention")


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    """A causal mixture-of-experts decoder (``backbone="decoder"``,
    models/decoder.py). The fields are the keys of the model's published
    ``config.json`` under their published names, then the share and the
    training sequence length.

    **The share.** A layer may be divided over ``share_count`` chips (tensor
    and expert parallel): each holds ``num_attention_heads`` query heads (or,
    layer by layer, ``num_attention_heads_per_layer``) with
    ``num_key_value_heads`` key-value heads, ``num_experts`` experts and
    ``vocab_size`` rows of the vocabulary — the counts HELD HERE, a
    ``share_count``-th of the published ones — and is share ``share_index`` of
    them. One exception: where the published key-value heads are fewer than
    the shares (4 heads over 8 chips), a key-value head is held by several
    shares, each with its own part of that head's query heads, and
    ``num_key_value_heads`` held is 1. The router keeps its published width
    (``num_experts * share_count`` outputs), its ``num_experts_per_tok`` and
    its renormalisation over all chosen experts, held or not; the layer
    computes the part of the attention and expert sums that its own heads and
    experts give. That partial result is what the layer returns: the
    all-reduce over the shares that completes it is the exchange, and on one
    chip the layer runs without it (``share_count=1`` is the whole model and
    needs none). What every share holds whole — norms, the router, a
    ``sparse_attention`` layer's indexer, a ``dense`` layer's MLP, the shared
    expert — is computed alike on each.

    **Layer types.** ``sliding_attention`` (plain RoPE, a window),
    ``full_attention`` (every earlier key of the document) and
    ``sparse_attention``: a query reads only the ``sa_config["topk"]`` keys a
    learned indexer scores highest (ops/sparse_attention.py), and the indexer
    is trained by a loss of its own (train/step.py:SequenceTask). The number
    of query heads may go by layer (``num_attention_heads_per_layer``: the
    Laguna family gives its window layers more heads than its full ones), the
    rotary embedding may cover the first ``partial_rotary_factor`` of a head
    only (a key of a layer type's ``rope_parameters``), and ``gating`` puts a
    sigmoid gate, one scalar a head, on the heads' outputs.

    **MLPs.** ``mlp_layer_types`` says layer by layer ``dense`` (one SiLU-gated
    MLP of ``intermediate_size``) or ``sparse`` (the routed experts; every
    layer where the list is empty). The router scores by ``scoring_func``
    (``softmax`` over all experts, or ``sigmoid`` of each logit), takes the
    ``num_experts_per_tok`` largest, renormalises over them
    (``norm_topk_prob``) and multiplies by ``moe_routed_scaling_factor``; a
    shared expert of ``shared_expert_intermediate_size`` (0: none) is added to
    the routed sum, unweighted."""

    hidden_size: int = 2304
    head_dim: int = 128
    num_attention_heads: int = 32
    # query heads layer by layer, where they differ (empty: num_attention_heads
    # on every layer)
    num_attention_heads_per_layer: Tuple[int, ...] = ()
    num_key_value_heads: int = 4
    num_hidden_layers: int = 28
    # one entry per layer: "sliding_attention" | "full_attention" |
    # "sparse_attention"
    layer_types: Tuple[str, ...] = (
        ("sliding_attention",) * 3 + ("full_attention",)
    ) * 7
    sliding_window: int = 1024
    num_experts: int = 64
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    norm_topk_prob: bool = True
    # "softmax" | "sigmoid": what the router's top-k is taken of (DeepSeek-V3's
    # key; a config that publishes a routed scaling factor and no score function
    # names it among what it assumes)
    scoring_func: str = "softmax"
    moe_routed_scaling_factor: float = 1.0
    # one entry per layer, "dense" | "sparse" (empty: every MLP is sparse), and
    # the dense MLP's width
    mlp_layer_types: Tuple[str, ...] = ()
    intermediate_size: int = 0
    # a shared expert beside the routed ones (0: none)
    shared_expert_intermediate_size: int = 0
    # a sigmoid gate on each head's output, from the layer's normalised input:
    # False, or as published True / "per-head"
    gating: object = False
    rms_norm_eps: float = 1e-6
    vocab_size: int = 98304
    # rope_parameters by layer type, each a sorted tuple of (key, value)
    # pairs (hashable: the config is a jit static); `rope()` gives the dict
    rope_parameters: Tuple[Tuple[str, Tuple[Tuple[str, object], ...]], ...] = (
        ("full_attention", (
            ("attention_factor", 1.2772588722239782), ("beta_fast", 32),
            ("beta_slow", 1), ("factor", 16),
            ("original_max_position_embeddings", 8192),
            ("rope_theta", 500000), ("rope_type", "yarn"),
        )),
        ("sliding_attention", (("rope_theta", 500000), ("rope_type", "default"))),
    )
    # the indexer of "sparse_attention" layers, under the published keys
    # (indexer_num_heads, indexer_head_dim, indexer_num_kv_heads, topk; chunk
    # sizes are read as tiles and change no result): sorted (key, value) pairs
    sa_config: Tuple[Tuple[str, int], ...] = ()
    # per-head RMS normalisation of q and k with a learned [head_dim] scale
    use_qk_norm: bool = False
    share_count: int = 1
    share_index: int = 0
    # tokens of one packed training sequence (data/tokens.py)
    sequence_length: int = 8192

    def __post_init__(self):
        if len(self.layer_types) < self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}"
            )
        unknown = set(self.layer_types) - set(DECODER_LAYER_TYPES)
        if unknown:
            raise ValueError(f"Unknown layer types {sorted(unknown)}")
        if "sparse_attention" in self.layer_types:
            sa = self.indexer
            missing = {"indexer_num_heads", "indexer_head_dim", "topk"} - set(sa)
            if missing:
                raise ValueError(f"sparse_attention layers need sa_config keys {sorted(missing)}")
            if sa.get("indexer_num_kv_heads", 1) != 1:
                raise ValueError("the indexer has one key head (indexer_num_kv_heads 1)")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of num_key_value_heads")
        for name in ("num_attention_heads_per_layer", "mlp_layer_types"):
            if getattr(self, name) and len(getattr(self, name)) < self.num_hidden_layers:
                raise ValueError(
                    f"{name} names {len(getattr(self, name))} layers, "
                    f"num_hidden_layers is {self.num_hidden_layers}"
                )
        if any(h % self.num_key_value_heads for h in self.num_attention_heads_per_layer):
            raise ValueError(
                "every entry of num_attention_heads_per_layer must be a multiple of "
                f"num_key_value_heads ({self.num_key_value_heads}): "
                f"{sorted(set(self.num_attention_heads_per_layer))}"
            )
        if set(self.mlp_layer_types) - {"dense", "sparse"}:
            raise ValueError(f"Unknown mlp layer types {sorted(set(self.mlp_layer_types))}")
        kept_mlps = self.mlp_layer_types[: self.num_hidden_layers]
        if "dense" in kept_mlps and not self.intermediate_size:
            raise ValueError("a dense MLP layer needs intermediate_size")
        if kept_mlps and "sparse" not in kept_mlps:
            raise ValueError("a mixture-of-experts decoder keeps at least one sparse layer")
        if self.scoring_func not in ("softmax", "sigmoid"):
            raise ValueError(f"Unknown scoring_func {self.scoring_func!r}")
        if self.gating not in (False, True, "per-head"):
            raise ValueError(f"Unknown gating {self.gating!r}: a gate is one scalar a head")
        if not 0 <= self.share_index < self.share_count:
            raise ValueError(
                f"share_index {self.share_index} is not one of {self.share_count} shares"
            )
        if self.num_experts_per_tok > self.num_experts * self.share_count:
            raise ValueError("num_experts_per_tok exceeds the experts routed over")

    @classmethod
    def from_published(cls, config: dict, **share) -> "DecoderConfig":
        """From a ``config.json`` dict (keys this class does not hold are
        passed over); ``share`` gives share_count, share_index,
        sequence_length."""
        names = {f.name for f in dataclasses.fields(cls)}
        # a published null (Keye's sliding_window) says nothing: the default stays
        kept = {k: v for k, v in config.items() if k in names and v is not None}
        kept["layer_types"] = tuple(config["layer_types"])
        for name in ("num_attention_heads_per_layer", "mlp_layer_types"):
            if name in kept:
                kept[name] = tuple(kept[name])
        rope = config.get("rope_parameters")
        if rope is None:
            # the older keys: one theta and one scaling for every layer
            scaling = {k: v for k, v in (config.get("rope_scaling") or {}).items()
                       if k not in ("type", "mrope_section")}
            scaling.setdefault("rope_type", "default")
            scaling["rope_theta"] = config["rope_theta"]
            rope = {kind: scaling for kind in set(kept["layer_types"])}
        # beside the layer types a published group may carry plain numbers
        # (Laguna's original_max_position_embeddings): they are no layer type's
        kept["rope_parameters"] = tuple(
            (kind, tuple(sorted(params.items())))
            for kind, params in sorted(rope.items()) if isinstance(params, dict)
        )
        if "sa_config" in config:
            kept["sa_config"] = tuple(sorted(config["sa_config"].items()))
        kept.update(share)
        return cls(**kept)

    def rope(self, layer_type: str) -> dict:
        return dict(dict(self.rope_parameters)[layer_type])

    def rotary_dim(self, layer_type: str) -> int:
        """The leading dimensions of a head that a layer type rotates."""
        return int(self.head_dim * self.rope(layer_type).get("partial_rotary_factor", 1))

    def heads(self, layer: int) -> int:
        """Query heads held of layer ``layer``."""
        per_layer = self.num_attention_heads_per_layer
        return per_layer[layer] if per_layer else self.num_attention_heads

    def mlp_type(self, layer: int) -> str:
        return self.mlp_layer_types[layer] if self.mlp_layer_types else "sparse"

    @property
    def indexer(self) -> dict:
        return dict(self.sa_config)


@dataclasses.dataclass(frozen=True)
class TokenStreamConfig:
    """The shape of the synthetic token stream the decoder trains on
    (data/tokens.py). Defaults: repository-length files, mostly longer than a
    1,024-token window."""

    median_length: float = 4096.0
    sigma: float = 1.0  # of the log of the length
    min_length: int = 64
    max_length: int = 8192
    zipf_exponent: float = 1.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters.

    Defaults mirror the reference's module constants (reference: model.py:13-24) and
    ``Model.__init__`` fallbacks (reference: model.py:63-106).
    """

    backbone: str = "resnet"  # "resnet" | "xception" | "vit" | "decoder"
    # l2 regularisation (reference: model.py:14 WEIGHT_DECAY = 0.001)
    weight_decay: float = 0.001
    # batch norm (reference: model.py:16-18)
    batch_norm_decay: float = 0.99
    batch_norm_epsilon: float = 0.001
    batch_norm_scale: bool = True
    # atrous output stride (reference: model.py:20 OUTPUT_STRIDE = 8)
    output_stride: int = 8
    # spatial input shape, channels excluded (reference: model.py:22 INPUT_SHAPE)
    input_shape: Tuple[int, int] = (101, 101)
    # input channels: image + Laplacian channel (reference: preprocessing.py:243)
    input_channels: int = 2
    # deepest residual stage width (reference: model.py:24 BASE_DEPTH = 256)
    base_depth: int = 256
    # residual units per stage before the atrous stage (reference: model.py:101-103)
    n_blocks: Tuple[int, ...] = (3, 4, 6)
    # residual-stage width family (backbone="resnet"): "reference" keeps the
    # reference's doubled stage widths — bottleneck 128/256/512 plus the
    # 1024-wide atrous multi-grid stage (reference: core/resnet.py:330-344),
    # ~3x the FLOPs of the standard model; "classic" is the standard
    # ResNet-50/101/152 ladder (bottleneck 64/128/256/512, four plain stages,
    # stride-32, no atrous stage) — the apples-to-apples architecture for
    # ImageNet benchmarks quoted against published ResNet-50 numbers. With
    # "classic", n_blocks has length 4 (e.g. (3, 4, 6, 3) = ResNet-50).
    block_layout: str = "reference"
    # "bottleneck" | "basic_block" (reference: model.py:104-106)
    block_type: str = "bottleneck"
    # Classification-path knobs (reference: core/resnet.py:246-256 kept a num_classes /
    # global_pool path alongside segmentation); None means segmentation head.
    num_classes: Optional[int] = None
    # compute dtype: params stay float32, activations/matmuls run in this dtype. TPU MXU
    # natively prefers bfloat16 — this is a TPU-first knob the reference had no analogue of.
    dtype: str = "float32"
    # route the ASPP's atrous depthwise convs through the Pallas VMEM kernel
    # (ops/pallas_kernels.py) instead of XLA's grouped conv; parameter trees are
    # identical between the two paths, so this is a pure execution-path switch.
    # Default OFF on STEP-LEVEL evidence (2026-08-01 v5e A/B, bf16 flagship,
    # best-of-3 40-step windows): pure XLA 37.95 ms/step vs 41.03 (Pallas at
    # rates >= 4, the old gate) vs 41.36 (all rates). The standalone kernel
    # genuinely beats XLA's grouped conv 1.46-1.61x per kernel
    # (bench_kernels.py, device-dominated protocol) — but inside the real
    # step XLA fuses depthwise+BN+ReLU chains, and the custom call forces
    # materialization that costs more than the kernel saves. The flag stays
    # for non-fused contexts; the dispatch remains rate- and platform-aware
    # (models/layers.py:DepthwiseConv2D).
    use_pallas_depthwise: bool = False
    # rematerialize residual units on the backward pass (jax.checkpoint): trades
    # recompute FLOPs for activation HBM — enables large per-chip batches.
    remat: bool = False
    # execute the root 3x3 stride-2 conv as a 2x2 conv on the
    # space-to-depth(2) input transform (models/layers.py:SpaceToDepthConv) —
    # numerically identical, but the MXU contracts over 4x the input channels
    # (12 vs 3 for RGB), the standard TPU stem trick. resnet/xception only;
    # requires even input dims; checkpoint-compatible with the plain stem
    # (the canonical 3x3 kernel is the stored parameter either way).
    stem_space_to_depth: bool = False
    # uniform channel-width scale for every backbone stage (root convs, residual
    # stages, Xception flows, ViT embed dim). 1.0 keeps the reference widths
    # (core/resnet.py:333-344, core/xception.py:405-465); fractional values give
    # width-scaled variants (Wide-ResNet-style scaling, and the knob that makes
    # tiny CI models actually tiny — the stage widths are otherwise fixed
    # constants).
    width_multiplier: float = 1.0
    # ViT family knobs (backbone="vit" — beyond-parity: the transformer
    # classifier that consumes parallel/ring_attention.py under sequence
    # parallelism; defaults are ViT-S/16).
    patch_size: int = 16
    embed_dim: int = 384
    vit_layers: int = 12
    num_heads: int = 6
    mlp_ratio: float = 4.0
    # route ViT attention through the fused Pallas block-attention kernel
    # (ops/flash_attention.py) instead of the XLA einsum path; parameter trees
    # are identical, so this is a pure execution-path switch. Ignored (with a
    # warning) under sequence_parallel>1, where the ring formulation owns the
    # attention math.
    use_fused_attention: bool = False
    # Switch-style mixture-of-experts (arXiv:2101.03961): every OTHER ViT
    # block's FFN becomes a top-1-routed MoE with this many experts (0 = dense;
    # backbone="vit" only). Trains with the load-balancing auxiliary loss on
    # any mesh (all experts local); TrainConfig.expert_parallel places one
    # expert per shard with all-to-all dispatch (parallel/expert.py).
    moe_experts: int = 0
    # per-expert capacity = ceil(tokens/E * factor); beyond-capacity tokens
    # pass through the residual (the standard fixed-shape trade)
    moe_capacity_factor: float = 1.25
    # weight of the sown load-balancing loss in the training objective (the
    # Switch paper's alpha = 0.01)
    moe_aux_weight: float = 0.01
    # backbone="decoder": the causal MoE decoder's own configuration
    # (DecoderConfig above); None for every image backbone
    decoder: Optional[DecoderConfig] = None

    def __post_init__(self):
        if self.backbone not in ("resnet", "xception", "vit", "decoder"):
            raise ValueError(f"Unknown backbone {self.backbone!r}")
        if (self.backbone == "decoder") != (self.decoder is not None):
            raise ValueError(
                "backbone='decoder' and a DecoderConfig go together: "
                f"backbone={self.backbone!r}, decoder={self.decoder!r}"
            )
        if self.block_type not in ("bottleneck", "basic_block"):
            raise ValueError(f"Unknown block type {self.block_type!r}")
        if self.dtype not in ("float32", "bfloat16"):
            raise ValueError(f"Unknown dtype {self.dtype!r}")
        if self.block_layout not in ("reference", "classic"):
            raise ValueError(f"Unknown block_layout {self.block_layout!r}")
        if self.block_layout == "classic":
            if self.backbone != "resnet":
                raise ValueError("block_layout='classic' applies to backbone='resnet' only")
            if len(self.n_blocks) != 4:
                raise ValueError(
                    "block_layout='classic' expects n_blocks of length 4, "
                    f"e.g. (3, 4, 6, 3) for ResNet-50; got {self.n_blocks}"
                )
        if self.width_multiplier <= 0:
            raise ValueError("width_multiplier must be positive")
        if self.stem_space_to_depth:
            if self.backbone == "vit":
                raise ValueError(
                    "stem_space_to_depth applies to conv stems "
                    "(backbone='resnet'/'xception'); ViT patchification already "
                    "folds pixels into the contraction"
                )
            if self.input_shape[0] % 2 or self.input_shape[1] % 2:
                raise ValueError(
                    "stem_space_to_depth needs even input dims, got "
                    f"{self.input_shape}"
                )
        if self.moe_experts < 0:
            raise ValueError(f"moe_experts must be >= 0, got {self.moe_experts}")
        if self.moe_experts:
            if self.backbone != "vit":
                raise ValueError(
                    "moe_experts requires backbone='vit' (the MoE FFN replaces "
                    "transformer-block MLPs)"
                )
            if self.vit_layers < 2:
                raise ValueError(
                    "moe_experts needs vit_layers >= 2 (every OTHER block is "
                    "MoE; a 1-layer stack would have none)"
                )


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Training-loop hyperparameters.

    Defaults mirror the reference's ``Model.__init__`` signature (reference:
    model.py:29-37) and its train-step constants: Adam with exponential decay — half the
    lr every 10 000 steps (reference: model.py:457-462), checkpoints every 500 steps
    (reference: model.py:118), eval throttled to >= 300 s (reference: model.py:214).
    """

    # "NHWC" | "NCHW" accepted at the API boundary for parity (reference: model.py:58-61).
    # NCHW is a SERVING/PREDICT boundary layout: serving_fn/export_serving take and
    # return [B, C, H, W] and predict() returns NCHW outputs. Training REJECTS it
    # (validate_training_data_format): the input pipelines feed NHWC by construction,
    # and on TPU the compute-layout motivation behind the reference's NCHW mode
    # ("about 10% faster" on GPU, model.py:45-46, transposed at model_fn top,
    # model.py:344-351) does not exist — XLA owns the internal layout.
    data_format: str = "NHWC"
    # "adam" reproduces the reference (tf.contrib AdamOptimizer, model.py:462);
    # "sgd" is Nesterov momentum — the standard ImageNet recipe behind the
    # 76%-top-1 north star (BASELINE.md); "lars" is layer-wise adaptive rate
    # scaling for large-batch training (You et al., arXiv:1708.03888 — the
    # published stabilizer for the 8k-batch preset).
    optimizer: str = "adam"
    sgd_momentum: float = 0.9
    # decoupled-from-the-loss weight decay applied inside the optimizer chain,
    # masked to conv/dense kernels only (BN scale/bias and biases stay
    # undecayed — the standard recipe, arXiv:1706.02677). For sgd it enters
    # before momentum+lr scaling, i.e. exactly the classic l2-SGD form; for
    # adam it switches the chain to AdamW; for lars it rides the trust-ratio
    # update. 0.0 reproduces the reference's EFFECTIVE objective (it declared
    # an l2 regularizer but never minimized it — reference: model.py:462-467,
    # core/resnet.py:357-376); the ImageNet presets set 1e-4 per their cited
    # recipe (configs.py).
    weight_decay: float = 0.0
    # exponential moving average of the parameters, tracked inside the
    # optimizer chain (train/step.py:ema_tracker) and used automatically for
    # eval and best-export when > 0 (train/step.py:with_ema_params). 0.0
    # disables (the reference's behavior: TF1/slim with no weight averaging);
    # ~0.9999 is the modern recipe value at ImageNet scale. Costs one extra
    # params-sized buffer in opt_state.
    ema_decay: float = 0.0
    # clip gradients to this global l2 norm before the optimizer update
    # (optax.clip_by_global_norm at the head of the chain, so decay/momentum
    # see the clipped gradient). 0.0 disables (the reference never clipped);
    # 1.0 is the standard ViT/large-LR stabilizer. Applies to every execution
    # strategy because it rides TrainState.tx.
    grad_clip_norm: float = 0.0
    # accumulate gradients over this many sequential microbatches inside each
    # train step (lax.scan), then apply ONE optimizer update on their mean —
    # effective batch = grad_accum_steps x fed batch at one microbatch's
    # activation memory. The optimizer step count (and therefore the lr
    # schedule) advances once per UPDATE, matching the semantics of feeding
    # the large batch directly. BN batch statistics are computed per
    # microbatch sequentially (the same per-shard locality the reference's
    # per-tower BN had). Standard data-parallel/spatial step only (the GSPMD
    # tensor-parallel and pipeline strategies define their own batch math).
    grad_accum_steps: int = 1
    # classification train-loss label smoothing (0.1 in the standard ImageNet
    # recipe, arXiv:1512.00567); eval metrics stay plain CE
    label_smoothing: float = 0.0
    # fit()'s on-device train augmentation policy: "flip_crop" (random mirror +
    # reflect-padded random crop — the ImageNet/CIFAR recipe and the default),
    # "crop" (no mirror — for chirality-sensitive classes: digits, text,
    # signage), "none" (stream batches untouched), "mixup" (flip_crop then
    # Beta(0.2)-convex image/label mixing, arXiv:1710.09412), or "cutmix"
    # (flip_crop then area-weighted box pasting, arXiv:1905.04899). The mixing
    # policies train against per-example paired CE (no soft-label buffers) and
    # require the standard data-parallel/tensor-parallel step (not
    # sequence/pipeline parallel). Eval is never augmented.
    augmentation: str = "flip_crop"
    # the decoder's synthetic token stream (TokenStreamConfig above); None is
    # its defaults, and what every image task leaves it at
    token_stream: Optional[TokenStreamConfig] = None
    lr: float = 0.001
    # "exponential" reproduces the reference's continuous decay (model.py:457-459);
    # "cosine" is the standard ImageNet recipe (linear warmup to `lr` over
    # `lr_warmup_steps`, cosine decay to ~0 over `lr_decay_steps`)
    lr_schedule: str = "exponential"
    # exponential: lr halves every `lr_decay_steps` (reference: model.py:457-459);
    # cosine: total decay horizon in steps
    lr_decay_steps: int = 10_000
    lr_decay_rate: float = 0.5
    lr_warmup_steps: int = 0
    # number of devices to use; None = all (reference: n_gpus, model.py:33)
    n_devices: Optional[int] = None
    # layout selection mode (parallel/planner.py): "explicit" runs the
    # degrees below verbatim (validated through the planner so indivisible
    # specs fail at parse time with a named constraint); "auto" derives the
    # whole (dp, tp, pp, spatial, zero1) layout from the model's exact
    # param/opt-state accounting, the per-chip HBM budget, and the device
    # topology — any degree explicitly set above its default stays PINNED
    # (explicit flags win) and the planner fills the rest. The chosen plan
    # rides the run-header ledger event either way.
    parallelism: str = "explicit"
    # per-chip HBM budget in GiB for the planner's feasibility gate; None
    # reads the backend's bytes_limit (CPU builds report none — the budget
    # gate then only fires when this is set)
    hbm_budget_gb: Optional[float] = None
    # sequence (spatial) parallel degree: shard the image H dimension over this
    # many devices per data-parallel replica (halo-exchange convs,
    # parallel/spatial.py). 1 = pure data parallelism (the reference's only mode).
    # A TPU-first capability for feature maps too large for one chip's HBM.
    sequence_parallel: int = 1
    # tensor (model) parallel degree: shard parameters/optimizer state over the
    # mesh's model axis via GSPMD annotations (parallel/tensor.py) — per-chip
    # param+optimizer memory drops by this factor; XLA places the collectives.
    # fit() only; mutually exclusive with sequence_parallel>1 (the GSPMD step
    # and the shard_map spatial step are different execution strategies).
    model_parallel: int = 1
    # pipeline parallel degree: run the ViT block stack as a K-stage GPipe
    # pipeline over the mesh's model axis (parallel/pipeline.py;
    # train/pipeline_step.py), each stage holding vit_layers/K consecutive
    # blocks, microbatches flowing stage-to-stage over one ppermute ICI hop
    # per tick. fit() + backbone="vit" only; mutually exclusive with
    # model_parallel>1 and sequence_parallel>1 (different execution
    # strategies over the same axes).
    pipeline_parallel: int = 1
    # microbatches per local batch for the GPipe schedule (bubble fraction
    # (K-1)/(M+K-1): set M >> K in production). None = pipeline_parallel
    # (correctness default).
    pipeline_microbatches: Optional[int] = None
    # expert parallel degree: place the MoE blocks' experts one-per-shard on
    # the mesh's model axis with all-to-all dispatch (parallel/expert.py).
    # Requires ModelConfig.moe_experts == expert_parallel and backbone="vit";
    # 1 computes every expert locally (dense dispatch, any mesh). Mutually
    # exclusive with the other model-axis strategies.
    expert_parallel: int = 1
    # ZeRO-1 cross-replica weight-update sharding (arXiv:2004.13336,
    # parallel/zero.py): optimizer state (Adam moments, LARS/SGD momentum,
    # the EMA tracker) shards over the data-parallel mesh axis — each leaf
    # partitioned on its largest dp-divisible dimension, tiny/indivisible
    # leaves replicated — and the weight update runs on each chip's 1/dp
    # shard under GSPMD constraints, with the parameter all-gather placed by
    # the partitioner. Per-chip optimizer memory drops by ~the data-parallel
    # degree (Adam slots are ~2x params; +1x more with ema_decay) at
    # neutral step time; numerics match the replicated update
    # (tests/test_zero1.py pins step-for-step equivalence). Composes with
    # grad_accum_steps, sequence_parallel, sync_batch_norm and
    # model_parallel (slots shard over (model, batch) jointly);
    # mutually exclusive with pipeline_parallel, whose stage runner owns its
    # own update placement.
    weight_update_sharding: bool = False
    # synchronized cross-shard BatchNorm: compute BN statistics over the
    # GLOBAL batch (lax.pmean over the batch mesh axis inside flax BN)
    # instead of per shard. Default False preserves the reference's
    # per-tower MirroredStrategy BN semantics; True is the cross-replica BN
    # standard on TPU pods when the per-shard batch gets small. Semantics
    # pinned against a full-batch single-device oracle
    # (tests/test_train_step.py::test_sync_batch_norm_matches_global_batch_oracle)
    # and measured worth +7.8 points of real accuracy at digits scale where
    # the per-shard batch is 8 (DIGITS_RUN.json 'xception_adam_syncbn':
    # 93.9% vs 86.1% per-shard; the chip's native full-batch BN scores
    # 96.4%). Composes with sequence_parallel
    # (stats span batch AND sequence shards); mutually exclusive with
    # pipeline_parallel, whose GPipe schedule owns BN microbatch-wise.
    sync_batch_norm: bool = False
    n_folds: int = 5
    seed: int = 42
    # best-model exports to keep (reference: model.py:37, 196-202)
    save_best: int = 5
    checkpoint_every_steps: int = 500
    eval_throttle_secs: int = 300
    # eval cadence in steps, decoupled from checkpointing and EXEMPT from
    # eval_throttle_secs (an explicit cadence is explicit user intent; same
    # semantics in Trainer and fit()). None preserves the reference's
    # train_and_evaluate shape: eval considered when a periodic checkpoint
    # lands AND the time throttle passed (reference: model.py:214)
    eval_every_steps: Optional[int] = None
    # train summaries every N steps / eval summaries every step (reference: model.py:470-481)
    train_log_every_steps: int = 20
    # write the JSONL run ledger ({workdir}/telemetry.jsonl, obs/ledger.py):
    # run header, per-window step events with the data-wait/compute split,
    # eval/checkpoint/memory snapshots, and post-warmup recompile flags —
    # the machine-readable record `telemetry-report` renders. Ledger writes
    # degrade to a warning on an unwritable workdir; disabling also skips the
    # span bookkeeping and the jax.monitoring compile listener.
    telemetry: bool = True
    # persistent XLA compile cache directory (utils/compile_cache.py): point
    # repeated runs at the same dir and a second same-shape run loads its
    # executables instead of recompiling. JAX_COMPILATION_CACHE_DIR, when
    # set, wins over this field; with neither, an accelerator run uses the
    # checkout's fixed .jax_cache_tpu and a CPU-pinned run does not cache.
    # An unwritable directory is an error. CLI: --compile-cache-dir.
    compile_cache_dir: Optional[str] = None
    # memory snapshot cadence, counted in LOG WINDOWS (every N-th window event
    # also records per-device HBM + host RSS); the trainers additionally
    # snapshot once after state init
    telemetry_memory_every_windows: int = 5
    # per-unit tracing (obs/trace.py): fraction of traces (one per top-level
    # span — each train step, eval pass, checkpoint save) persisted as
    # `trace` ledger events, exportable via `telemetry-report --export-trace`
    # as Chrome/Perfetto trace-event JSON. 0.0 disables tracing entirely
    # (zero per-step cost); 1.0 keeps every span. Sampling is decided per
    # TRACE at its root, so sampled traces are always complete. Overhead
    # with tracing fully on is gated <= 2% step time (bench.py
    # --trace-overhead, CI).
    trace_sample_rate: float = 0.0
    # continuous profiling cadence (obs/profiler.py), counted in LOG WINDOWS:
    # every N-th window boundary captures a short windowed jax.profiler trace
    # (a few steps, stopped early), parses it through utils/xplane.py into a
    # per-op roofline classification, and ledgers `profile_capture` +
    # `op_roofline` events the planner's measured-costs loop and the live
    # console read. 0 (default) disables cadence capture entirely; triggered
    # captures (health alerts, serve /admin/profile) are independent of it.
    # Overhead with the cadence on is gated <= 2% step time (bench.py
    # --profile-overhead, CI).
    profile_every_windows: int = 0
    # online health monitors (obs/health.py) over the per-window telemetry:
    # NaN/Inf loss guard, rolling median+MAD loss-spike detector, step-time
    # regression vs the first clean windows. Alerts land as structured
    # `health_alert` ledger events and render in telemetry-report's health
    # section.
    health_monitors: bool = True
    # NaN/Inf loss guard action: "warn" alerts and keeps training, "abort"
    # alerts then raises HealthAbortError (stop at a recorded boundary
    # instead of training on garbage), "off" disables just this guard.
    # Drill it with --inject-fault nan-loss@N (resilience/faults.py).
    nan_guard: str = "warn"
    # overlap periodic Orbax saves with subsequent train steps (background
    # serialization); best exports and resume points still synchronize
    async_checkpointing: bool = False
    # host→device input prefetch depth (data/pipeline.py:device_prefetch):
    # the producer thread stays this many PLACED batches ahead of the train
    # loop so HBM copies overlap the previous step's compute — the
    # generalized form of the reference's prefetch(2×n_gpus)
    # (reference: model.py:319-320). Per-window queue-depth telemetry makes
    # underruns visible in telemetry-report; raise this when they show.
    prefetch_depth: int = 2
    # host–device overlap budget (train/async_loop.py): the host may run at
    # most this many dispatched-but-unretired train steps ahead of the
    # device, and log windows defer their metric fetch one window
    # (copy_to_host_async at the boundary, fetched while the next window is
    # already dispatching) — the device queue never drains on a log line.
    # The blocked-past-budget time is ledgered as the fetch_wait span.
    # 0 = the synchronous legacy loop (blocking device_get per log window);
    # numerics are bit-identical either way (tests/test_async_loop.py,
    # BENCH_ASYNC.json).
    dispatch_ahead_steps: int = 2
    # parallel input-service workers (data/service.py): N background
    # read+decode workers execute the index-keyed global-shuffle batch plan
    # and hand batches back in order — record-sharded training streams scale
    # past the single reader thread, and the K-fold trainer's in-memory fold
    # streams assemble off the host loop. Batch CONTENT is worker-count
    # invariant (the plan is a pure function of the seed), so this knob is
    # pure throughput. 0 = the legacy in-line streams (records.py batches /
    # pipeline.train_batches) with their seed-folded resume.
    data_service_workers: int = 2
    # fit() with record shards and NO val split: hold out this fraction of the
    # train record shards (at least one) as the eval split, so best-checkpoint
    # selection runs on data the model never trains on. 0.0 keeps every shard
    # in training and falls back to evaluating one pass over the train records
    # (with a loud warning — train-set top-1 as the selection signal silently
    # overfits).
    eval_holdout_fraction: float = 0.0

    def __post_init__(self):
        if self.data_format not in ("NCHW", "NHWC"):
            raise ValueError(
                f"Unknown data format {self.data_format}. Has to be either NCHW or NHWC"
            )
        if self.parallelism not in ("explicit", "auto"):
            raise ValueError(
                "parallelism must be 'explicit' or 'auto', got "
                f"{self.parallelism!r}"
            )
        if self.hbm_budget_gb is not None and self.hbm_budget_gb <= 0:
            raise ValueError(
                f"hbm_budget_gb must be positive, got {self.hbm_budget_gb}"
            )
        if self.sequence_parallel < 1:
            raise ValueError(
                f"sequence_parallel must be >= 1, got {self.sequence_parallel}"
            )
        if self.model_parallel < 1:
            raise ValueError(
                f"model_parallel must be >= 1, got {self.model_parallel}"
            )
        if self.model_parallel > 1 and self.sequence_parallel > 1:
            raise ValueError(
                "model_parallel and sequence_parallel cannot both exceed 1: "
                "the GSPMD tensor-parallel step and the shard_map spatial step "
                "are different execution strategies"
            )
        if self.pipeline_parallel < 1:
            raise ValueError(
                f"pipeline_parallel must be >= 1, got {self.pipeline_parallel}"
            )
        if self.pipeline_parallel > 1 and (
            self.model_parallel > 1 or self.sequence_parallel > 1
        ):
            raise ValueError(
                "pipeline_parallel cannot combine with model_parallel or "
                "sequence_parallel: the GPipe stage runner, the GSPMD "
                "tensor-parallel step, and the shard_map spatial step are "
                "different execution strategies over the same mesh axes"
            )
        if self.pipeline_microbatches is not None and (
            self.pipeline_microbatches < self.pipeline_parallel
            or self.pipeline_parallel == 1
        ):
            raise ValueError(
                "pipeline_microbatches requires pipeline_parallel > 1 and at "
                "least one microbatch per stage "
                f"(got microbatches={self.pipeline_microbatches}, "
                f"stages={self.pipeline_parallel})"
            )
        if self.weight_update_sharding and self.pipeline_parallel > 1:
            raise ValueError(
                "weight_update_sharding cannot combine with pipeline_parallel: "
                "the GPipe stage runner applies its own update placement "
                "(train/pipeline_step.py); ZeRO-1 shards the data axis the "
                "standard and GSPMD steps own"
            )
        if self.sync_batch_norm and self.pipeline_parallel > 1:
            raise ValueError(
                "sync_batch_norm cannot combine with pipeline_parallel: the "
                "GPipe schedule computes BN statistics microbatch-wise per "
                "stage (train/pipeline_step.py)"
            )
        if self.expert_parallel < 1:
            raise ValueError(
                f"expert_parallel must be >= 1, got {self.expert_parallel}"
            )
        if self.expert_parallel > 1 and (
            self.model_parallel > 1
            or self.sequence_parallel > 1
            or self.pipeline_parallel > 1
        ):
            raise ValueError(
                "expert_parallel cannot combine with model_parallel, "
                "sequence_parallel, or pipeline_parallel: each owns the "
                "model/sequence mesh axes as a different execution strategy"
            )
        if self.augmentation not in ("flip_crop", "crop", "none", "mixup", "cutmix"):
            raise ValueError(f"Unknown augmentation {self.augmentation!r}")
        if self.augmentation in ("mixup", "cutmix") and (
            self.sequence_parallel > 1 or self.pipeline_parallel > 1
        ):
            raise ValueError(
                f"augmentation={self.augmentation!r} pairs examples through "
                "extra per-example batch fields (labels_b/lam), which the "
                "sequence-parallel and pipeline execution strategies do not "
                "thread; use the data/tensor-parallel step"
            )
        if self.lr_schedule not in ("exponential", "cosine"):
            raise ValueError(f"Unknown lr_schedule {self.lr_schedule!r}")
        if self.optimizer not in ("adam", "sgd", "lars"):
            raise ValueError(f"Unknown optimizer {self.optimizer!r}")
        if self.weight_decay < 0:
            raise ValueError(f"weight_decay must be >= 0, got {self.weight_decay}")
        if not 0.0 <= self.ema_decay < 1.0:
            raise ValueError(
                f"ema_decay must be in [0, 1), got {self.ema_decay}"
            )
        if self.grad_clip_norm < 0:
            raise ValueError(
                f"grad_clip_norm must be >= 0, got {self.grad_clip_norm}"
            )
        if self.grad_accum_steps < 1:
            raise ValueError(
                f"grad_accum_steps must be >= 1, got {self.grad_accum_steps}"
            )
        if self.grad_accum_steps > 1 and (
            self.model_parallel > 1 or self.pipeline_parallel > 1
        ):
            raise ValueError(
                "grad_accum_steps > 1 runs inside the shard_map "
                "data/spatial-parallel step; the GSPMD tensor-parallel and "
                "pipeline strategies define their own batch math"
            )
        # cadence knobs are modulus divisors in the train loops
        # (`step_no % knob`): a zero would surface as a ZeroDivisionError
        # mid-run, hours in — reject it at construction instead
        if self.train_log_every_steps < 1:
            raise ValueError(
                "train_log_every_steps must be >= 1, got "
                f"{self.train_log_every_steps}"
            )
        if self.checkpoint_every_steps < 1:
            raise ValueError(
                "checkpoint_every_steps must be >= 1, got "
                f"{self.checkpoint_every_steps}"
            )
        if self.eval_every_steps is not None and self.eval_every_steps < 1:
            raise ValueError(
                "eval_every_steps must be >= 1 (or None for the "
                f"checkpoint-coupled default), got {self.eval_every_steps}"
            )
        if self.eval_throttle_secs < 0:
            raise ValueError(
                f"eval_throttle_secs must be >= 0, got {self.eval_throttle_secs}"
            )
        if self.prefetch_depth < 1:
            raise ValueError(
                f"prefetch_depth must be >= 1, got {self.prefetch_depth} "
                "(1 = single-buffered; there is no unprefetched mode)"
            )
        if self.data_service_workers < 0:
            raise ValueError(
                "data_service_workers must be >= 0 (0 = the legacy in-line "
                f"input streams), got {self.data_service_workers}"
            )
        if self.dispatch_ahead_steps < 0:
            raise ValueError(
                "dispatch_ahead_steps must be >= 0 (0 = the synchronous "
                f"host loop), got {self.dispatch_ahead_steps}"
            )
        if self.telemetry_memory_every_windows < 1:
            raise ValueError(
                "telemetry_memory_every_windows must be >= 1, got "
                f"{self.telemetry_memory_every_windows}"
            )
        if not 0.0 <= self.trace_sample_rate <= 1.0:
            raise ValueError(
                "trace_sample_rate must be in [0, 1] (0 disables tracing), "
                f"got {self.trace_sample_rate}"
            )
        if self.profile_every_windows < 0:
            raise ValueError(
                "profile_every_windows must be >= 0 (0 disables cadence "
                f"profiling), got {self.profile_every_windows}"
            )
        if self.nan_guard not in ("warn", "abort", "off"):
            raise ValueError(
                "nan_guard must be one of ('warn', 'abort', 'off'), got "
                f"{self.nan_guard!r}"
            )
        if not 0.0 <= self.eval_holdout_fraction < 1.0:
            raise ValueError(
                "eval_holdout_fraction must be in [0, 1), got "
                f"{self.eval_holdout_fraction}"
            )


def validate_training_data_format(cfg: TrainConfig) -> None:
    """Reject NCHW at the TRAINING boundary (serving/predict honor it).

    The reference trained in NCHW because it was ~10% faster on its GPUs
    (reference: model.py:45-46, 344-351). On TPU that motivation does not
    exist — XLA chooses the internal layout — and the framework's input
    pipelines feed NHWC by construction, so accepting NCHW for training would
    be a silently-ignored knob. Train NHWC; NCHW remains fully honored where
    user-facing arrays actually cross the boundary: ``serving_fn``,
    ``export_serving``, and ``predict`` outputs."""
    if cfg.data_format == "NCHW":
        raise ValueError(
            "data_format='NCHW' applies to the serving/predict boundary only; "
            "training input is NHWC by construction (on TPU, XLA owns the "
            "compute layout — the reference's NCHW-for-speed mode, "
            "model.py:45-46, has no TPU analogue). Train with NHWC, then "
            "construct a Trainer with data_format='NCHW' over the same "
            "model_dir for NCHW serving/prediction."
        )
