"""Operations and bytes of a decoder cell, from the configuration file, the
traffic file and the step's own counters (the ``step_window`` fields
``attn_keys_per_query`` and ``moe_pairs``) — never from the program's model
code, and not from XLA's ``cost_analysis()``, which changes with the
implementation.

Model FLOPs are the matrix work of the share this chip holds: the q, k, v and
o projections, the attention products over the (query, key) pairs the mask
leaves, the three expert products over the (token, expert) pairs routed here,
the router and the vocabulary head. A train step is three times the forward
pass (forward, and the two products of each backward); recomputation is not
counted. Norms, rotary embedding, softmaxes, sort and gather, loss and
optimizer are not counted.
"""

from __future__ import annotations

from typing import Dict, List

BF16 = 2  # bytes an element


def layer_kinds(cfg: dict) -> List[str]:
    return list(cfg["layer_types"][: cfg["num_hidden_layers"]])


def tokens_per_step(traffic: dict) -> int:
    return int(traffic["global_batch"]) * int(traffic["sequence_length"])


def projection_flops(cfg: dict, tokens: int) -> float:
    """q, k, v and o of one layer."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return 2.0 * tokens * d * (q + 2 * kv + q)


def attention_flops(cfg: dict, pairs: float) -> float:
    """QK^T and PV over ``pairs`` unmasked (query, key) pairs, every query
    head held: 2 * head_dim operations each."""
    return 4.0 * cfg["head_dim"] * cfg["num_attention_heads"] * pairs


def attention_bytes(cfg: dict, tokens: int) -> float:
    """What one pass of the kernel has to move once: q and the output
    [tokens, heads, head_dim], k and v [tokens, key-value heads, head_dim]."""
    hd = cfg["head_dim"]
    return BF16 * tokens * hd * (2.0 * cfg["num_attention_heads"] + 2.0 * cfg["num_key_value_heads"])


def expert_product_flops(cfg: dict, pairs: float) -> float:
    """One of the three expert products (gate, up, down) over ``pairs``
    (token, expert) pairs."""
    return 2.0 * pairs * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_product_bytes(cfg: dict, pairs: float) -> float:
    """Rows in, rows out and the held experts' matrices, once."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return BF16 * (pairs * (d + f) + cfg["num_experts"] * d * f)


def router_flops(cfg: dict, tokens: int) -> float:
    return 2.0 * tokens * cfg["hidden_size"] * cfg["num_experts"] * cfg["share"]["n"]


def head_flops(cfg: dict, tokens: int) -> float:
    return 2.0 * tokens * cfg["hidden_size"] * cfg["vocab_size"]


def attention_pairs(keys_per_query: Dict[str, float], tokens: int) -> Dict[str, float]:
    """Unmasked (query, key) pairs of a step by layer type, from the mean
    keys a query sees."""
    return {kind: float(k) * tokens for kind, k in keys_per_query.items()}


def step_flops(cfg: dict, traffic: dict, keys_per_query: Dict[str, float],
               moe_pairs_per_step: float) -> Dict[str, float]:
    """Model FLOPs of one train step by part (forward x 3). ``moe_pairs_per_step``
    counts every layer's pairs together, as the window counter does."""
    tokens = tokens_per_step(traffic)
    pairs = attention_pairs(keys_per_query, tokens)
    kinds = layer_kinds(cfg)
    parts = {
        "projections": len(kinds) * projection_flops(cfg, tokens),
        "attention": sum(attention_flops(cfg, pairs[kind]) for kind in kinds),
        "experts": 3.0 * expert_product_flops(cfg, moe_pairs_per_step),
        "router": len(kinds) * router_flops(cfg, tokens),
        "head": head_flops(cfg, tokens),
    }
    parts = {k: 3.0 * v for k, v in parts.items()}
    parts["total"] = sum(parts.values())
    return parts


def experts_floor_s(cfg: dict, moe_pairs_per_step: float, peaks) -> float:
    """The least time one chip can take over a step's grouped expert
    products: per layer three products, each forward, gradient of the rows
    and gradient of the matrices — nine products with one operation count;
    for each the larger of operations over peak FLOP/s and bytes over peak
    bytes/s."""
    layers = cfg["num_hidden_layers"]
    per_layer = moe_pairs_per_step / layers
    one = max(
        expert_product_flops(cfg, per_layer) / peaks.bf16_flops,
        expert_product_bytes(cfg, per_layer) / peaks.hbm_bytes_per_s,
    )
    return layers * 9.0 * one


def attention_floor_s(cfg: dict, traffic: dict, keys_per_query: Dict[str, float], peaks) -> float:
    """The least time over a step's attention kernels, counting only the
    unmasked pairs: per layer the forward pass and a backward pass of twice
    its operations, which reads q, k, v, the output and its gradient and
    writes three gradients."""
    tokens = tokens_per_step(traffic)
    pairs = attention_pairs(keys_per_query, tokens)
    total = 0.0
    for kind in layer_kinds(cfg):
        work = attention_flops(cfg, pairs[kind])
        moved = attention_bytes(cfg, tokens)
        total += max(work / peaks.bf16_flops, moved / peaks.hbm_bytes_per_s)
        total += max(2.0 * work / peaks.bf16_flops, 2.5 * moved / peaks.hbm_bytes_per_s)
    return total
