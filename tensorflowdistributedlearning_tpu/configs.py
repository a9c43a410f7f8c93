"""Named config registry — the BASELINE.json config ladder as one-call presets.

The reference's "configs" were notebook cells (SURVEY §5.6: batch 64, 10 000 steps,
2 GPUs, 5 folds hard-coded in Untitled.ipynb/Test.ipynb). Here every supported
configuration is a named ``(ModelConfig, TrainConfig)`` preset covering the
BASELINE.json ladder: CIFAR smoke -> ImageNet ResNet-50/101/152 + Xception-41 DP ->
bf16 large-batch pod config, plus the reference's own TGS-salt segmentation run.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

from tensorflowdistributedlearning_tpu.config import (
    DecoderConfig,
    ModelConfig,
    TokenStreamConfig,
    TrainConfig,
)


@dataclasses.dataclass(frozen=True)
class Preset:
    model: ModelConfig
    train: TrainConfig
    global_batch: int
    description: str


def _imagenet_model(**kw) -> ModelConfig:
    base = dict(
        num_classes=1000,
        input_shape=(224, 224),
        input_channels=3,
        output_stride=None,  # standard stride-32 classification trunk
        dtype="bfloat16",
    )
    base.update(kw)
    return ModelConfig(**base)


# 90 epochs of ImageNet-1k at global batch 1024 (1.28M images): the standard
# ResNet recipe behind the 76%-top-1 north star (BASELINE.md) — SGD Nesterov
# momentum 0.9, lr linearly scaled 0.1 x (batch/256) = 0.4, 5-epoch linear
# warmup, cosine decay to ~0, weight decay 1e-4 on kernels only
# (Goyal et al., arXiv:1706.02677).
_IMAGENET_1K_TRAIN = TrainConfig(
    optimizer="sgd",
    lr=0.4,
    lr_schedule="cosine",
    lr_warmup_steps=6_255,
    lr_decay_steps=112_590,
    label_smoothing=0.1,
    weight_decay=1e-4,
)

PRESETS: Dict[str, Preset] = {
    # the reference's production config: TGS salt segmentation, 5-fold, batch 64,
    # Adam 1e-3 halving each 10k steps (reference: model.py:33, 457-462;
    # Untitled.ipynb cells 7-8)
    "tgs_salt": Preset(
        model=ModelConfig(),
        train=TrainConfig(),
        global_batch=64,
        description="Reference parity: ResNet-v2-beta + DeepLabV3+ head, 101x101x2, "
        "5-fold CV, Lovász hinge (reference: model.py defaults)",
    ),
    "tgs_salt_bf16": Preset(
        model=ModelConfig(dtype="bfloat16"),
        train=TrainConfig(),
        global_batch=64,
        description="TPU-native variant of the reference workload: identical "
        "architecture/loss with bf16 compute (params, loss, and metrics stay "
        "f32; convs/matmuls run at the MXU's bf16 rate)",
    ),
    # BASELINE.json "ResNet-50 single-tower CIFAR-10 (CPU smoke test)"
    "cifar10_smoke": Preset(
        model=ModelConfig(
            num_classes=10,
            input_shape=(32, 32),
            input_channels=3,
            n_blocks=(1, 1, 1),
            base_depth=64,
            output_stride=None,
        ),
        train=TrainConfig(n_folds=2, checkpoint_every_steps=100),
        global_batch=64,
        description="CIFAR-10-shaped smoke config runnable on a CPU mesh",
    ),
    # the elastic/resilience drill shape: one step is milliseconds on a CPU
    # mesh, checkpoints land every 2 steps (dense resume points for
    # kill-and-resize drills), and every step writes a ledger window (the
    # straggler probe needs per-step cross-host comparisons). Micro-sized on
    # purpose: tests/bench_elastic drive REAL multi-process worlds with it.
    "elastic_smoke": Preset(
        model=ModelConfig(
            num_classes=4,
            input_shape=(16, 16),
            input_channels=3,
            n_blocks=(1, 1, 1),
            base_depth=8,
            width_multiplier=0.0625,
            output_stride=None,
        ),
        train=TrainConfig(
            checkpoint_every_steps=2,
            train_log_every_steps=1,
            augmentation="none",
        ),
        global_batch=8,
        description="Micro classification config for elastic-resize and "
        "kill-drill runs: millisecond steps on a CPU mesh, checkpoint "
        "every 2 steps, a ledger window every step",
    ),
    # BASELINE.json "ResNet-50 multi-tower data-parallel (ImageNet-1k)"
    "resnet50_imagenet": Preset(
        model=_imagenet_model(n_blocks=(3, 4, 6)),
        train=_IMAGENET_1K_TRAIN,
        global_batch=1024,
        description="ResNet-50 ImageNet-1k data-parallel, bf16",
    ),
    # Standard-width ResNet-50: the published 25.6M-param architecture that
    # ImageNet numbers (and BASELINE.md's 360 images/sec/chip V100 figure)
    # actually quote. The reference-family presets above run the reference's
    # ~3x-FLOPs wide layout (doubled stage widths + atrous stage,
    # reference: core/resnet.py:330-344); this one is the apples-to-apples
    # benchmark architecture.
    "resnet50_classic_imagenet": Preset(
        model=_imagenet_model(
            n_blocks=(3, 4, 6, 3),
            block_layout="classic",
            # measured ON (2026-08-01 v5e window): 2308.1 img/s/chip vs
            # 2281.16 with the plain stem (+1.2%, MFU 0.3357 vs 0.331);
            # logits are bitwise-equivalent (tests/test_space_to_depth.py)
            stem_space_to_depth=True,
        ),
        train=_IMAGENET_1K_TRAIN,
        global_batch=1024,
        description="Standard ResNet-50 (classic 64/128/256/512 widths) "
        "ImageNet-1k data-parallel, bf16, space-to-depth stem",
    ),
    # BASELINE.json "ResNet-101 / ResNet-152 deeper variants"
    "resnet101_imagenet": Preset(
        model=_imagenet_model(n_blocks=(3, 4, 23)),
        train=_IMAGENET_1K_TRAIN,
        global_batch=1024,
        description="ResNet-101 ImageNet-1k data-parallel, bf16",
    ),
    "resnet152_imagenet": Preset(
        model=_imagenet_model(n_blocks=(3, 8, 36)),
        train=_IMAGENET_1K_TRAIN,
        global_batch=1024,
        description="ResNet-152 ImageNet-1k data-parallel, bf16",
    ),
    # BASELINE.json "Xception multi-tower data-parallel (ImageNet-1k)"
    "xception41_imagenet": Preset(
        model=_imagenet_model(backbone="xception"),
        train=_IMAGENET_1K_TRAIN,
        global_batch=1024,
        description="Xception-41 ImageNet-1k data-parallel, bf16 (the backbone the "
        "reference shipped broken, fixed here — SURVEY §2.4.8-10)",
    ),
    # Beyond-parity: ViT-S/16 — the transformer classifier whose attention runs
    # as ring attention under sequence_parallel (parallel/ring_attention.py)
    "vit_s16_imagenet": Preset(
        model=_imagenet_model(
            backbone="vit",
            patch_size=16,
            embed_dim=384,
            vit_layers=12,
            num_heads=6,
            # measured ON (2026-08-01 device-dominated microbench): train
            # step is a tie, long-seq forward wins 1.14x, no measured
            # downside; the dispatch degrades to XLA above seq 1024 and
            # off-TPU (models/vit.py:_FUSED_MAX_SEQ)
            use_fused_attention=True,
        ),
        # transformers keep Adam (SGD momentum trains ViTs poorly); standard
        # lr 1e-3 + long warmup, sharing the 90-epoch cosine horizon; with
        # weight_decay the chain is AdamW — wd 0.1 is the DeiT/ViT-S recipe
        # (arXiv:2012.12877)
        train=dataclasses.replace(
            _IMAGENET_1K_TRAIN,
            optimizer="adam",
            lr=0.001,
            lr_warmup_steps=10_000,
            weight_decay=0.1,
            # global-norm clip 1.0 — the ViT/DeiT training stabilizer
            # (arXiv:2010.11929 App. B.1; rides the optimizer chain)
            grad_clip_norm=1.0,
        ),
        global_batch=1024,
        description="ViT-S/16 ImageNet-1k, bf16; sequence-parallelizable via "
        "ring attention (--sequence-parallel)",
    ),
    # Beyond-parity: Switch-style MoE ViT — every other block's FFN is a
    # top-1-routed 8-expert MoE with the load-balancing auxiliary loss
    # (arXiv:2101.03961); ~4x the FFN capacity of ViT-S at ~1x the per-token
    # FLOPs. Train data-parallel anywhere, or --expert-parallel 8 to place
    # one expert per chip with all-to-all dispatch.
    "vit_s16_moe_imagenet": Preset(
        model=_imagenet_model(
            backbone="vit",
            patch_size=16,
            embed_dim=384,
            vit_layers=12,
            num_heads=6,
            moe_experts=8,
            # same measured flip as vit_s16_imagenet (seq-gated, TPU-only)
            use_fused_attention=True,
        ),
        train=dataclasses.replace(
            _IMAGENET_1K_TRAIN,
            optimizer="adam",
            lr=0.001,
            lr_warmup_steps=10_000,
            weight_decay=0.1,
            # global-norm clip 1.0 — the ViT/DeiT training stabilizer
            # (arXiv:2010.11929 App. B.1; rides the optimizer chain)
            grad_clip_norm=1.0,
        ),
        global_batch=1024,
        description="ViT-S/16 Switch-MoE (8 experts, top-1 routing + load-"
        "balancing loss) ImageNet-1k, bf16; expert-parallelizable "
        "(--expert-parallel 8)",
    ),
    # BASELINE.json "ResNet-50 bfloat16 large-batch (8k) on v5e-64 pod"
    "resnet50_bf16_8k": Preset(
        model=_imagenet_model(n_blocks=(3, 4, 6), remat=True),
        # LARS with layer-wise trust ratios is what holds accuracy at batch 8k
        # (You et al., arXiv:1708.03888; the MLPerf ResNet recipe): base lr
        # linear-scaled to the batch, 10-epoch warmup, cosine decay, wd 1e-4
        # masked to kernels (BN/bias excluded from decay AND trust scaling)
        train=TrainConfig(
            optimizer="lars",
            lr=3.2,
            lr_schedule="cosine",
            lr_warmup_steps=1_564,   # 10 epochs
            lr_decay_steps=14_080,
            label_smoothing=0.1,
            weight_decay=1e-4,
            async_checkpointing=True,
            # ZeRO-1: at dp=64 the replicated LARS momentum + master math is
            # pure waste — shard the slots and the update across the data
            # axis (parallel/zero.py; numerics pinned identical by
            # tests/test_zero1.py, per-chip bytes recorded by bench.py)
            weight_update_sharding=True,
        ),
        global_batch=8192,
        description="ResNet-50 bf16 large-batch (8k) pod config (v5e-64: 128/chip), "
        "LARS optimizer, ZeRO-1 weight-update sharding",
    ),
    # Mellum2-12B-A2.5B (https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct
    # config.json): every width as published; one chip's share of a layer
    # divided over four (8 of 32 query heads with 1 of 4 key-value heads, 16
    # of 64 experts, 24,576 of 98,304 vocabulary rows) and one period of the
    # layer pattern (sliding x3, full) of the 28 layers — 531.5M parameters
    # here, 8.5 GB with gradients and Adam's moments. Assumed (the config has
    # no key): AdamW 3e-7 / 0.1, no q/k norm, no auxiliary router loss.
    "mellum2_12b_a2p5b_share4": Preset(
        model=ModelConfig(
            backbone="decoder",
            dtype="bfloat16",
            decoder=DecoderConfig(
                num_hidden_layers=4,
                layer_types=(("sliding_attention",) * 3 + ("full_attention",)) * 7,
                num_attention_heads=8,
                num_key_value_heads=1,
                num_experts=16,
                # as published: every MLP sparse, so the dense width serves no layer
                mlp_layer_types=("sparse",) * 28,
                intermediate_size=7168,
                vocab_size=24576,
                share_count=4,
                share_index=0,
                sequence_length=8192,
            ),
        ),
        # the rate is small on purpose: these are random weights. At 3e-4
        # every token took the same experts within a few steps on the chip,
        # routers trained or frozen (PERF.md §6, PR 26) — by the update's
        # arithmetic, not traced: the first thing random weights learn is the
        # unigram distribution, as one vector added to every position of the
        # residual stream (the head has no bias), and it soon outgrows the
        # embedding. 110 steps at 3e-7 leave the stream, and so the routing,
        # where the weights put them; pass --lr for trained weights
        train=TrainConfig(optimizer="adam", lr=3e-7, weight_decay=0.1, augmentation="none"),
        global_batch=2,
        description="Mellum2-12B-A2.5B decoder (top-8-of-64 experts, window/full "
        "attention 3:1, YaRN), next-token training on packed 8,192-token "
        "sequences: chip 0's share of a layer divided over 4 chips, 4 of 28 "
        "layers — a partial result by design (config.py:DecoderConfig)",
    ),
    # Keye-VL-2.0-30B-A3B's language model (model_type KeyeVL2,
    # https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json;
    # the published keys are in perfbench/configs/keye_vl2_30b_a3b_share8.json):
    # every layer is grouped-query attention over the 2,048 keys a learned
    # indexer picks (DeepSeek sparse attention), then 128 routed experts, 8 a
    # token. Published widths; chip 0's share of a layer divided over 8 chips
    # (4 of 32 query heads on 1 of 4 key-value heads — a key-value head is
    # held by two shares — 16 of 128 experts, 18,992 of 151,936 vocabulary
    # rows; the indexer and the router whole), 4 of the 48 alike layers —
    # 400.4M parameters here, 6.4 GB with gradients and Adam's moments.
    # Assumed (the config has no key): q/k RMS normalisation, the indexer's
    # LayerNorm, rotary embedding and scales as DeepSeek's implementation has
    # them, its alignment loss at weight 1, AdamW 3e-7 / 0.1.
    "keye_vl2_30b_a3b_share8": Preset(
        model=ModelConfig(
            backbone="decoder",
            dtype="bfloat16",
            decoder=DecoderConfig(
                hidden_size=2048,
                num_hidden_layers=4,
                layer_types=("sparse_attention",) * 48,
                num_attention_heads=4,
                num_key_value_heads=1,
                num_experts=16,
                moe_intermediate_size=768,
                intermediate_size=6144,  # published; no layer is dense
                vocab_size=18992,
                rope_parameters=(
                    ("sparse_attention", (("rope_theta", 10000000), ("rope_type", "default"))),
                ),
                sa_config=(
                    ("indexer_head_dim", 64), ("indexer_num_heads", 16),
                    ("indexer_num_kv_heads", 1), ("kv_chunk_size", 512),
                    ("q_chunk_size", 512), ("topk", 2048),
                ),
                use_qk_norm=True,
                share_count=8,
                share_index=0,
                sequence_length=16384,
            ),
        ),
        # the rate is small for the reason the Mellum-2 preset gives; documents
        # are long, so that most queries see more keys than they may read
        # a window every 10 steps: a step takes half a second
        train=TrainConfig(
            optimizer="adam", lr=3e-7, weight_decay=0.1, augmentation="none",
            train_log_every_steps=10,
            token_stream=TokenStreamConfig(
                median_length=16384.0, sigma=1.0, min_length=1024, max_length=16384
            ),
        ),
        global_batch=1,
        description="Keye-VL-2.0-30B-A3B language model (attention over the 2,048 keys "
        "a learned indexer picks, top-8-of-128 experts), next-token training on "
        "packed 16,384-token sequences of long documents: chip 0's share of a "
        "layer divided over 8 chips, 4 of 48 layers — a partial result by "
        "design (config.py:DecoderConfig)",
    ),
    # Laguna-XS.2 (poolside, model_type laguna,
    # https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json; the
    # published keys are in perfbench/configs/laguna_xs2_33b_a3b_share8.json):
    # full and window layers 1:3 with head counts of their own (48 / 64 query
    # heads on 8 key-value heads), a sigmoid gate on every head's output, YaRN
    # over the first half of a head on full layers and plain RoPE over the
    # whole on window layers (window 512); a leading dense MLP of 8,192, then
    # 256 routed experts of 512, 8 a token by sigmoid scores renormalised over
    # the chosen and scaled by 2.5, beside one shared expert. Published
    # widths; chip 0's share of a layer divided over 8 chips (6 / 8 query
    # heads on 1 key-value head, 32 of 256 experts, 12,544 of 100,352
    # vocabulary rows; the router, the shared expert and layer 0's dense MLP
    # whole), layers 0-4 of 40 — 540.6M parameters here, 8.65 GB with
    # gradients and Adam's moments. Assumed (the config has no key): the
    # gate's form, sigmoid scores with renormalisation, no router bias and no
    # auxiliary loss, no q/k norm, AdamW 3e-7 / 0.1.
    "laguna_xs2_33b_a3b_share8": Preset(
        model=ModelConfig(
            backbone="decoder",
            dtype="bfloat16",
            decoder=DecoderConfig(
                hidden_size=2048,
                num_hidden_layers=5,
                layer_types=(("full_attention",) + ("sliding_attention",) * 3) * 10,
                num_attention_heads=6,
                num_attention_heads_per_layer=(6, 8, 8, 8) * 10,
                num_key_value_heads=1,
                sliding_window=512,
                num_experts=32,
                moe_intermediate_size=512,
                scoring_func="sigmoid",
                moe_routed_scaling_factor=2.5,
                mlp_layer_types=("dense",) + ("sparse",) * 39,
                intermediate_size=8192,
                shared_expert_intermediate_size=512,
                gating=True,
                vocab_size=12544,
                rope_parameters=(
                    ("full_attention", (
                        ("attention_factor", 1.4158883083359672), ("beta_fast", 64),
                        ("beta_slow", 1), ("factor", 64),
                        ("original_max_position_embeddings", 4096),
                        ("partial_rotary_factor", 0.5),
                        ("rope_theta", 500000), ("rope_type", "yarn"),
                    )),
                    ("sliding_attention", (
                        ("partial_rotary_factor", 1), ("rope_theta", 10000),
                        ("rope_type", "default"),
                    )),
                ),
                share_count=8,
                share_index=0,
                sequence_length=16384,
            ),
        ),
        # the rate is small for the reason the Mellum-2 preset gives; a window
        # every 10 steps, as the Keye preset has it
        train=TrainConfig(
            optimizer="adam", lr=3e-7, weight_decay=0.1, augmentation="none",
            train_log_every_steps=10,
            token_stream=TokenStreamConfig(
                median_length=8192.0, sigma=1.0, min_length=256, max_length=16384
            ),
        ),
        global_batch=1,
        description="Laguna-XS.2 decoder (window/full attention 3:1 with 64/48 query heads "
        "under a per-head output gate, a leading dense MLP, a shared expert and "
        "sigmoid-routed top-8-of-256 experts of 512), next-token training on packed "
        "16,384-token sequences: chip 0's share of a layer divided over 8 chips, "
        "layers 0-4 of 40 — a partial result by design (config.py:DecoderConfig)",
    ),
    # Kimi-Linear-48B-A3B (moonshotai, model_type kimi_linear,
    # https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json;
    # the published keys are in perfbench/configs/kimi_linear_48b_a3b_share32.json):
    # Kimi Delta Attention (32 heads of 128, short convolutions of 4, a decay
    # by channel; ops/kda.py) on three layers in four and multi-head latent
    # attention without a rotary part (32 heads, q.k 192, v 128, a 512-wide
    # compressed key-value) on the fourth; a leading dense MLP of 9,216, then
    # 256 routed experts of 1,024, 8 a token by sigmoid scores renormalised and
    # scaled by 2.446, beside one shared expert. Published widths; chip 0 of
    # 32 sharing each expert layer by experts (8 of 256), attention, layer 0's
    # MLP, the shared expert and the router whole on every chip, 20,480 of
    # 163,840 vocabulary rows (divided over 8), layers 0-4 of 27 — 602.4M
    # parameters here, 9.64 GB with gradients and Adam's moments. Assumed (the
    # config has no key): the KDA's scale, convolutions and initialisation, no
    # router bias and no auxiliary loss, AdamW 3e-7 / 0.1.
    "kimi_linear_48b_a3b_share32": Preset(
        model=ModelConfig(
            backbone="decoder",
            dtype="bfloat16",
            decoder=DecoderConfig(
                hidden_size=2304,
                head_dim=72,
                num_attention_heads=32,
                num_key_value_heads=32,
                num_hidden_layers=5,
                layer_types=(("linear_attention",) * 3 + ("latent_attention",)) * 6
                + ("linear_attention",) * 2 + ("latent_attention",),
                num_experts=8,
                num_experts_per_tok=8,
                moe_intermediate_size=1024,
                scoring_func="sigmoid",
                moe_routed_scaling_factor=2.446,
                mlp_layer_types=("dense",) + ("sparse",) * 26,
                intermediate_size=9216,
                shared_expert_intermediate_size=1024,
                rms_norm_eps=1e-5,
                vocab_size=20480,
                rope_parameters=(
                    ("latent_attention", (("rope_theta", 10000), ("rope_type", "default"))),
                    ("linear_attention", (("rope_theta", 10000), ("rope_type", "default"))),
                ),
                linear_attn_config=(("head_dim", 128), ("num_heads", 32),
                                    ("short_conv_kernel_size", 4)),
                kv_lora_rank=512,
                qk_nope_head_dim=128,
                qk_rope_head_dim=64,
                v_head_dim=128,
                mla_use_nope=True,
                share_count=32,
                share_index=0,
                sequence_length=16384,
            ),
        ),
        # the rate is small for the reason the Mellum-2 preset gives; a window
        # every 10 steps, as the Keye preset has it
        train=TrainConfig(
            optimizer="adam", lr=3e-7, weight_decay=0.1, augmentation="none",
            train_log_every_steps=10,
            token_stream=TokenStreamConfig(
                median_length=8192.0, sigma=1.0, min_length=256, max_length=16384
            ),
        ),
        global_batch=1,
        description="Kimi-Linear-48B-A3B decoder (Kimi Delta Attention on three layers in "
        "four, latent attention without rotation on the fourth, a leading dense MLP, a "
        "shared expert and sigmoid-routed top-8-of-256 experts of 1,024), next-token "
        "training on packed 16,384-token sequences: chip 0 of 32 sharing each expert "
        "layer by experts, attention whole, layers 0-4 of 27 — a partial result by design "
        "(config.py:DecoderConfig)",
    ),
}


def get_preset(name: str) -> Preset:
    if name not in PRESETS:
        raise ValueError(
            f"Unknown preset {name!r}; available: {sorted(PRESETS)}"
        )
    return PRESETS[name]


def resnet_depth_blocks(depth: int) -> Tuple[int, int, int]:
    """Stage sizes for the standard ResNet depths (units before the 3-unit atrous/
    final stage, matching the reference's (3,4,6)=ResNet-50 convention,
    reference: model.py:101-103, core/resnet.py:330-344)."""
    table = {50: (3, 4, 6), 101: (3, 4, 23), 152: (3, 8, 36)}
    if depth not in table:
        raise ValueError(f"Unsupported ResNet depth {depth}; choose from {sorted(table)}")
    return table[depth]
