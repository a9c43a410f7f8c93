"""Operations and bytes of a Kimi-Linear cell — Kimi Delta Attention and
latent attention beside the routed experts — from the configuration file, the
traffic file and the step's own counters (the ``step_window`` fields
``attn_keys_per_query`` and ``moe_pairs``), never from the program's model
code.

**KDA** is priced in the chunkwise form at ``CHUNK`` = 64 tokens, whatever
chunk the program uses. Per chunk and head, with K = V the head size: the two
decayed products over the chunk's pairs (k.k and q.k, ``2 C^2 K`` each), the
unit lower-triangular inverse (``2 C^3 / 3``), ``W = T (beta k e^G)`` and
``U~ = T (beta v)`` (``2 C^2 K``, ``2 C^2 V``), ``U = U~ - W S`` (``2 C K
V``), ``O = (q e^G) S + M U`` (``2 C K V + 2 C^2 V``) and ``S' = e^G S +
k^T U`` (``2 C K V``). A train step is the forward once and a backward of
twice its operations; the bytes are q, k, v and o (the compute type), g and
beta (float32) once forward, twice backward.

**Latent attention**: q.k over ``qk_nope_head_dim + qk_rope_head_dim`` and
p.v over ``v_head_dim`` on every head, over the (query, key) pairs the step
counted (``attn_keys_per_query``), forward once and backward at twice.

Model FLOPs are the matrix work of the share this chip holds, layer by layer:
the projections of each mixer (KDA's q, k, v, o, the decay's and the output
gate's two each and beta's; MLA's q, compressed key-value, its expansion and
o), the KDA recurrence as above, MLA's attention products, the dense MLP, the
shared expert, the router, the three expert products over the (token,
expert) pairs routed here, and the vocabulary head. A train step is three
times the forward pass; recomputation is not counted, nor what is
elementwise (convolutions, norms, gates, softmaxes, sort and gather, loss,
optimizer).
"""

from __future__ import annotations

from typing import Dict, List

from perfbench import flops_lm

CHUNK = 64
F32 = 4
BF16 = flops_lm.BF16
tokens_per_step = flops_lm.tokens_per_step


def layer_kinds(cfg: dict) -> List[str]:
    """``linear`` or ``latent``, layer by layer (``kda_layers`` is 1-indexed)."""
    kda = cfg["linear_attn_config"]["kda_layers"]
    return ["linear" if i + 1 in kda else "latent" for i in range(cfg["num_hidden_layers"])]


def mlp_kinds(cfg: dict) -> List[str]:
    first, every = cfg["first_k_dense_replace"], cfg["moe_layer_freq"]
    return ["sparse" if i >= first and i % every == 0 else "dense"
            for i in range(cfg["num_hidden_layers"])]


def _kda_dims(cfg: dict):
    lin = cfg["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"]


def kda_flops(cfg: dict, tokens: int) -> float:
    """The recurrence's operations of one KDA layer's forward pass."""
    h, k = _kda_dims(cfg)
    c = CHUNK
    chunk = 4 * c * c * k + 2 * c**3 / 3 + 2 * c * c * k + 2 * c * c * k + 6 * c * k * k \
        + 2 * c * c * k
    return h * chunk * tokens / c


def kda_bytes(cfg: dict, tokens: int) -> float:
    """What one forward pass of a KDA layer's kernel moves once: q, k, v and
    o in the compute type, g and beta in float32."""
    h, k = _kda_dims(cfg)
    return tokens * h * (4 * k * BF16 + k * F32 + F32)


def kda_floor_s(cfg: dict, traffic: dict, peaks) -> float:
    """The least time over a step's KDA kernels: per layer the forward pass
    and a backward pass of twice its operations and bytes, each the larger of
    operations over peak FLOP/s and bytes over peak bytes/s."""
    tokens = tokens_per_step(traffic)
    one = max(kda_flops(cfg, tokens) / peaks.bf16_flops,
              kda_bytes(cfg, tokens) / peaks.hbm_bytes_per_s)
    return layer_kinds(cfg).count("linear") * 3.0 * one


def latent_flops(cfg: dict, pairs: float) -> float:
    """q.k and p.v of one latent layer over ``pairs`` (query, key) pairs."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return 2.0 * pairs * cfg["num_attention_heads"] * (qk + cfg["v_head_dim"])


def latent_bytes(cfg: dict, tokens: int) -> float:
    """q, k, v and the output of one latent layer, once, in the compute type."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return BF16 * tokens * cfg["num_attention_heads"] * (2 * qk + 2 * cfg["v_head_dim"])


def latent_floor_s(cfg: dict, traffic: dict, keys_per_query: float, peaks) -> float:
    """The least time over a step's latent attention kernels, counting only
    the unmasked pairs: per layer the forward pass and a backward pass of
    twice its operations, which reads q, k, v, the output and its gradient
    and writes three gradients (``flops_lm.attention_floor_s``'s rule)."""
    tokens = tokens_per_step(traffic)
    work = latent_flops(cfg, keys_per_query * tokens)
    moved = latent_bytes(cfg, tokens)
    one = max(work / peaks.bf16_flops, moved / peaks.hbm_bytes_per_s) \
        + max(2.0 * work / peaks.bf16_flops, 2.5 * moved / peaks.hbm_bytes_per_s)
    return layer_kinds(cfg).count("latent") * one


def linear_projection_flops(cfg: dict, tokens: int) -> float:
    h, k = _kda_dims(cfg)
    d = cfg["hidden_size"]
    return 2.0 * tokens * (4 * d * h * k + 2 * (d * k + k * h * k) + d * h)


def latent_projection_flops(cfg: dict, tokens: int) -> float:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rank = cfg["kv_lora_rank"]
    return 2.0 * tokens * (d * h * (nope + rope) + d * (rank + rope) + rank * h * (nope + vd)
                           + h * vd * d)


def gated_mlp_flops(cfg: dict, tokens: int, width: int) -> float:
    return 3.0 * 2.0 * tokens * cfg["hidden_size"] * width


def step_flops(cfg: dict, traffic: dict, keys_per_query: Dict[str, float],
               moe_pairs_per_step: float) -> Dict[str, float]:
    """Model FLOPs of one train step by part (forward x 3). ``moe_pairs_per_step``
    counts every sparse layer's pairs together, as the window counter does."""
    tokens = tokens_per_step(traffic)
    kinds, mlps = layer_kinds(cfg), mlp_kinds(cfg)
    linear, latent, sparse = kinds.count("linear"), kinds.count("latent"), mlps.count("sparse")
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    parts = {
        "projections": linear * linear_projection_flops(cfg, tokens)
        + latent * latent_projection_flops(cfg, tokens),
        "kda": linear * kda_flops(cfg, tokens),
        "attention": latent * latent_flops(
            cfg, keys_per_query.get("latent_attention", 0.0) * tokens),
        "experts": 3.0 * 2.0 * moe_pairs_per_step * d * f,
        "shared": sparse * gated_mlp_flops(cfg, tokens, f * cfg["num_shared_experts"]),
        "dense": (len(mlps) - sparse) * gated_mlp_flops(cfg, tokens, cfg["intermediate_size"]),
        "router": sparse * 2.0 * tokens * d * cfg["num_experts"] * cfg["share"]["n"],
        "head": flops_lm.head_flops(cfg, tokens),
    }
    parts = {k: 3.0 * v for k, v in parts.items()}
    parts["total"] = sum(parts.values())
    return parts
