"""Operations and bytes of a decoder cell whose layers are
``sparse_attention``, from the configuration file, the traffic file and the
step's own counters (the ``step_window`` fields ``sparse_pairs_scored``,
``sparse_pairs_selected`` and ``moe_pairs``) — never from the program's model
code. What the layer shares with the other decoder (q, k, v, o projections,
experts, router, head) is ``flops_lm``'s; here is what the indexer adds.

Counted as model work, per ``sparse_attention`` layer:

- the indexer's three projections (16 x 64 queries, one 64-wide key, 16
  weights a position), forward and the two products of the backward pass;
- the indexer's scores: forward ``2 x heads x dim`` operations over every
  *visible* (query, key) pair — every one has to be scored before any can be
  left out; backward the two products (to the queries, to the key) over the
  *selected* pairs only, since ``L_I``'s gradient is zero elsewhere;
- attention over the *selected* pairs, ``flops_lm.attention_flops``, x 3.

``L_I`` itself, the selection, ReLU and the weighted sum over heads are
elementwise and, like softmaxes and norms in ``flops_lm``, not counted; nor
is recomputation.
"""

from __future__ import annotations

from typing import Dict

from perfbench import flops_lm

BF16, F32 = 2, 4


def sparse_layers(cfg: dict) -> int:
    return flops_lm.layer_kinds(cfg).count("sparse_attention")


def indexer_projection_flops(cfg: dict, tokens: int) -> float:
    """The indexer's queries, key and weights of one layer, forward."""
    sa = cfg["sa_config"]
    heads, dim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return 2.0 * tokens * cfg["hidden_size"] * (heads * dim + dim + heads)


def indexer_score_flops(cfg: dict, pairs: float) -> float:
    """One product of every indexer head's query with the key, over ``pairs``
    (query, key) pairs: forward over the visible ones; each of the two
    backward products over the selected ones."""
    sa = cfg["sa_config"]
    return 2.0 * sa["indexer_num_heads"] * sa["indexer_head_dim"] * pairs


def indexer_bytes(cfg: dict, tokens: int) -> float:
    """What one pass over the indexer's scores has to move once: the queries
    [tokens, heads, dim] and the key [tokens, dim] in bfloat16, the weights
    [tokens, heads] in float32, and the selection going out as ``topk`` key
    positions a query (the scores themselves need not leave the chip's fast
    memory)."""
    sa = cfg["sa_config"]
    heads, dim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return tokens * (BF16 * (heads * dim + dim) + F32 * heads + 4.0 * sa["topk"])


def step_flops(cfg: dict, traffic: dict, scored_per_step: float, selected_per_step: float,
               moe_pairs_per_step: float) -> Dict[str, float]:
    """Model FLOPs of one train step by part. ``scored_per_step`` and
    ``selected_per_step`` count every sparse layer's pairs together, as the
    window counters do."""
    tokens = flops_lm.tokens_per_step(traffic)
    layers = sparse_layers(cfg)
    keys = {"sparse_attention": selected_per_step / max(layers, 1) / tokens}
    parts = flops_lm.step_flops(cfg, traffic, keys, moe_pairs_per_step)
    del parts["total"]
    parts["indexer_projections"] = 3.0 * layers * indexer_projection_flops(cfg, tokens)
    parts["indexer_scores"] = (indexer_score_flops(cfg, scored_per_step)
                               + 2.0 * indexer_score_flops(cfg, selected_per_step))
    parts["total"] = sum(parts.values())
    return parts


def indexer_floor_s(cfg: dict, traffic: dict, scored_per_step: float, selected_per_step: float,
                    peaks) -> float:
    """The least time over a step's indexer scores: per layer the forward pass
    over the visible pairs and a backward pass of two products over the
    selected ones; for each the larger of operations over peak FLOP/s and
    bytes over peak bytes/s."""
    tokens = flops_lm.tokens_per_step(traffic)
    layers = max(sparse_layers(cfg), 1)
    moved = indexer_bytes(cfg, tokens)
    forward = indexer_score_flops(cfg, scored_per_step / layers)
    backward = 2.0 * indexer_score_flops(cfg, selected_per_step / layers)
    return layers * (
        max(forward / peaks.bf16_flops, moved / peaks.hbm_bytes_per_s)
        + max(backward / peaks.bf16_flops, 2.0 * moved / peaks.hbm_bytes_per_s)
    )


def attention_floor_s(cfg: dict, traffic: dict, selected_per_step: float, peaks) -> float:
    """``flops_lm.attention_floor_s`` over the selected pairs: a kernel that
    computes every visible pair takes longer than this asks."""
    tokens = flops_lm.tokens_per_step(traffic)
    keys = {"sparse_attention": selected_per_step / max(sparse_layers(cfg), 1) / tokens}
    return flops_lm.attention_floor_s(cfg, traffic, keys, peaks)
