"""Reductions a decoder cell adds to ``xtrace.py``'s: which part of the step
an op belongs to, told from the shapes in its HLO text (the trace's op events
carry no scope), and the step's own counters from the ledger's windows.

The sizes come from the configuration and traffic files:

- an **attention kernel** is a custom call that moves a tensor whose last two
  dimensions are (sequence length, head size) — q, k, v, the output and their
  gradients as the Pallas kernel takes them — and none of the hidden width;
- an **expert product** is a custom call that reads or writes the held
  experts' matrices ``[experts, hidden, expert width]`` (either order);
- **moe** is the expert products and every other op over the sorted pair
  buffer (``tokens x experts per token`` rows: sort, gather, activation,
  combine) or over the router's ``[tokens, experts routed over]`` outputs;
- **head_loss** is every op that moves a tensor with the vocabulary held as
  a dimension beside the hidden width or a token chunk (the head's products,
  the softmax and its gradient) — not the optimizer's update of the head.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from perfbench import xtrace

_SHAPE = re.compile(r"\b(?:bf16|f16|f32|s8|u8|s32|u32|pred)\[(\d+(?:,\d+)*)\]")


def _shapes(op_text: str) -> List[Tuple[int, ...]]:
    return [tuple(int(d) for d in m.group(1).split(",")) for m in _SHAPE.finditer(op_text)]


def sizes(cell) -> Dict[str, int]:
    cfg, traffic = cell.config, cell.traffic
    tokens = int(traffic["global_batch"]) * int(traffic["sequence_length"])
    return {
        "seq": int(traffic["sequence_length"]), "tokens": tokens,
        "hd": cfg["head_dim"], "d": cfg["hidden_size"], "f": cfg["moe_intermediate_size"],
        "e": cfg["num_experts"], "e_all": cfg["num_experts"] * cfg["share"]["n"],
        "v": cfg["vocab_size"], "k": cfg["num_experts_per_tok"],
        "rows": tokens * cfg["num_experts_per_tok"],
    }


def part_of(op_text: str, z: Dict[str, int]) -> str:
    """"attention", "moe_experts", "moe_other", "head_loss", "other" — or
    "container" for a ``while``: the ops of its body are events of their own
    inside it (the head's loss is a scan over token chunks), so its own span
    would count them twice."""
    if xtrace.short_name(op_text).lstrip("%").startswith(("while", "conditional")):
        return "container"
    shapes = _shapes(op_text.split(", kind=", 1)[0])
    custom = " custom-call(" in op_text
    matrices = {(z["e"], z["d"], z["f"]), (z["e"], z["f"], z["d"])}
    if custom and any(s in matrices for s in shapes):
        return "moe_experts"
    if custom and any(len(s) >= 2 and s[-2:] == (z["seq"], z["hd"]) for s in shapes) \
            and not any(z["d"] in s for s in shapes):
        return "attention"
    pair_rows = ((z["rows"],), (z["tokens"], z["k"]))  # sorted, or still by token
    if any(s[: len(lead)] == lead for s in shapes for lead in pair_rows) or any(
        s == (z["tokens"], z["e_all"]) for s in shapes
    ):
        return "moe_other"
    # logits and what is computed from them; not the embedding table
    # [vocabulary, hidden], and not the optimizer's update of the head, whose
    # shapes are all the head's own
    if any(len(s) >= 2 and z["v"] in s and s not in ((z["v"], z["d"]), (z["d"], z["v"]))
           for s in shapes):
        return "head_loss"
    if any(s == (z["d"], z["v"]) for s in shapes) and any(
        len(s) == 2 and s[1] == z["d"] and s[0] != z["v"] for s in shapes
    ):
        return "head_loss"  # the head's matrix beside activations
    return "other"


def part_seconds(run) -> Optional[Tuple[Dict[str, float], int]]:
    """(device seconds by part inside the step program, its whole executions)
    on device 0 of the traced part; None without a trace or a step in it."""
    if run.trace is None:
        return None
    needle = run.cell.traffic["programs"]["step"]
    _, calls = xtrace.module_time_s(run.trace, needle)
    if not calls:
        return None
    z = sizes(run.cell)
    out: Dict[str, float] = {}
    for name, _, dur in xtrace.ops_inside(run.trace, needle):
        part = part_of(name, z)
        if part != "container":
            out[part] = out.get(part, 0.0) + dur / 1e9
    return out, calls


# -- the step's own counters ---------------------------------------------------


def counters(run) -> Optional[Dict[str, object]]:
    """Per step, over the ledger windows inside the measured window:
    ``moe_pairs`` (all layers together), ``keys_per_query`` by layer type
    (weighted by the windows' steps) and the worst ``moe_load_max_over_mean``.
    None where the program writes no such fields."""
    windows = [w for w in run.windows if "moe_pairs" in w and "attn_keys_per_query" in w]
    steps = sum(w["steps"] for w in windows)
    if not steps:
        return None
    kinds = windows[0]["attn_keys_per_query"]
    return {
        "moe_pairs": sum(w["moe_pairs"] for w in windows) / steps,
        "moe_pairs_dropped": sum(w.get("moe_pairs_dropped", 0) for w in windows),
        "keys_per_query": {
            kind: sum(w["attn_keys_per_query"][kind] * w["steps"] for w in windows) / steps
            for kind in kinds
        },
        "load_max_over_mean": max(w["moe_load_max_over_mean"] for w in windows),
    }
