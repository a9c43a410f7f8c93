"""Operations and bytes of a decoder cell whose layers differ — head counts
by layer, a per-head gate, a dense MLP layer, a shared expert beside the
routed ones — from the configuration file, the traffic file and the step's
own counters (the ``step_window`` fields ``attn_keys_per_query`` and
``moe_pairs``), never from the program's model code.

Model FLOPs are the matrix work of the share this chip holds, layer by
layer: the q, k, v and o projections with the layer's own head count, the
gate's product, the attention products over the (query, key) pairs the mask
leaves on the layer's heads, the three expert products over the (token,
expert) pairs routed here, the router, the shared expert and a dense layer's
MLP over every token, and the vocabulary head. A train step is three times
the forward pass; recomputation is not counted, nor what is elementwise
(norms, rotary embedding, softmaxes, the gate's sigmoid, sort and gather,
loss, optimizer).
"""

from __future__ import annotations

import math
from typing import Dict, List

from perfbench import flops_lm

# as the program cuts its sorted pair buffer (parallel/expert.py): rows of a
# grouped product's tile, and a segment over the pairs expected here
ROW_TILE = 512
SEGMENT_OVER_EXPECTED = 1.25

tokens_per_step = flops_lm.tokens_per_step
layer_kinds = flops_lm.layer_kinds


def layer_heads(cfg: dict) -> List[int]:
    return list(cfg["num_attention_heads_per_layer"][: cfg["num_hidden_layers"]])


def mlp_kinds(cfg: dict) -> List[str]:
    return list(cfg["mlp_layer_types"][: cfg["num_hidden_layers"]])


def sparse_layers(cfg: dict) -> int:
    return mlp_kinds(cfg).count("sparse")


def heads_of_kind(cfg: dict, kind: str) -> List[int]:
    """The head count of each layer of one attention kind."""
    return [h for h, k in zip(layer_heads(cfg), layer_kinds(cfg)) if k == kind]


def segment_rows(cfg: dict, traffic: dict) -> int:
    """Rows of one segment of the program's sorted pair buffer."""
    pairs = tokens_per_step(traffic) * cfg["num_experts_per_tok"]
    expected = pairs / cfg["share"]["n"]
    return min(pairs, math.ceil(expected * SEGMENT_OVER_EXPECTED / ROW_TILE) * ROW_TILE)


def _of_heads(cfg: dict, heads: int, **other) -> dict:
    """``cfg`` as ``flops_lm`` reads it for a layer of ``heads`` query heads."""
    return dict(cfg, num_attention_heads=heads, **other)


def projection_flops(cfg: dict, tokens: int, heads: int) -> float:
    """q, k, v, o and the gate of one layer of ``heads`` query heads."""
    gate = 2.0 * tokens * cfg["hidden_size"] * heads if cfg.get("gating") else 0.0
    return flops_lm.projection_flops(_of_heads(cfg, heads), tokens) + gate


def attention_flops(cfg: dict, pairs: float, heads: int) -> float:
    """QK^T and PV over ``pairs`` unmasked (query, key) pairs on ``heads``
    query heads."""
    return flops_lm.attention_flops(_of_heads(cfg, heads), pairs)


def gated_mlp_flops(cfg: dict, tokens: int, width: int) -> float:
    """The three products of one SiLU-gated MLP of ``width`` over every token."""
    return 3.0 * 2.0 * tokens * cfg["hidden_size"] * width


def step_flops(cfg: dict, traffic: dict, keys_per_query: Dict[str, float],
               moe_pairs_per_step: float) -> Dict[str, float]:
    """Model FLOPs of one train step by part (forward x 3). ``moe_pairs_per_step``
    counts every sparse layer's pairs together, as the window counter does."""
    tokens = tokens_per_step(traffic)
    pairs = flops_lm.attention_pairs(keys_per_query, tokens)
    kinds, heads, sparse = layer_kinds(cfg), layer_heads(cfg), sparse_layers(cfg)
    parts = {
        "projections": sum(projection_flops(cfg, tokens, h) for h in heads),
        "attention": sum(attention_flops(cfg, pairs[k], h) for k, h in zip(kinds, heads)),
        "experts": 3.0 * flops_lm.expert_product_flops(cfg, moe_pairs_per_step),
        "shared": sparse * gated_mlp_flops(cfg, tokens, cfg["shared_expert_intermediate_size"]),
        "dense": (len(kinds) - sparse) * gated_mlp_flops(cfg, tokens, cfg["intermediate_size"]),
        "router": sparse * flops_lm.router_flops(cfg, tokens),
        "head": flops_lm.head_flops(cfg, tokens),
    }
    parts = {k: 3.0 * v for k, v in parts.items()}
    parts["total"] = sum(parts.values())
    return parts


def attention_floor_s(cfg: dict, traffic: dict, kind: str, keys_per_query: float, peaks) -> float:
    """``flops_lm.attention_floor_s`` (per layer the forward pass and a
    backward pass of twice its operations, each the larger of operations over
    peak FLOP/s and bytes over peak bytes/s, over the unmasked pairs) summed
    over the layers of one attention kind, each on its own head count."""
    return sum(
        flops_lm.attention_floor_s(
            _of_heads(cfg, heads, num_hidden_layers=1, layer_types=[kind]), traffic,
            {kind: keys_per_query}, peaks)
        for heads in heads_of_kind(cfg, kind))


def experts_floor_s(cfg: dict, moe_pairs_per_step: float, peaks) -> float:
    """``flops_lm.experts_floor_s`` over the sparse layers alone (a dense
    layer has no grouped product): nine products a layer, one operation count
    each."""
    return flops_lm.experts_floor_s(
        dict(cfg, num_hidden_layers=sparse_layers(cfg)), moe_pairs_per_step, peaks)
