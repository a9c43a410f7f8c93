"""Reductions a sparse decoder cell adds to ``lm_trace.py``'s: which part of
a ``sparse_attention`` layer an op of the step belongs to, told from the
shapes in its HLO text, and the layer's counters from the ledger's windows.

With ``T`` the sequence length, the layer's ops are those that move a
``[T, T]`` tensor: the indexer's scores, the loss's gradient to them, and
what is made from either.

- **indexer**: a custom call that reads the indexer's queries
  ``[heads, T, dim]`` beside a ``[T, T]`` tensor — the scores, forward and
  backward;
- **align**: a custom call over ``[.., T, head size]`` tensors that *writes* a
  float ``[T, T]`` tensor (the attention's probabilities against the scores'
  softmax: the loss's gradient to the scores), and any other op that writes a
  float ``[T, T]`` tensor or takes an exponential or a logarithm over one —
  that gradient scaled by the loss's cotangent on its way to the indexer's
  backward kernels;
- **attention**: every other custom call over ``[.., T, head size]`` beside
  the ``[T, T]`` scores, with none of the hidden width — the kernels that
  attend over the selection, forward, dq and dkv;
- **select**: every other op over ``[T, T]``: the threshold search (a custom
  call that reads the scores and writes integers a query) and the search of
  the tie positions, whose ops read the scores and write integers or flags.

A ``conditional``, like a ``while``, is a container: its branch's ops are
events of their own. Everything else goes by ``lm_trace.part_of`` (experts,
head, other).
"""

from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

from perfbench import lm_trace, xtrace

SPARSE_PARTS = ("indexer", "select", "attention", "align")
_OP_CALL = re.compile(r" [a-z][\w\-]*\(")  # where an op's own name ends what it writes


def sizes(cell) -> Dict[str, int]:
    z = lm_trace.sizes(cell)
    sa = cell.config.get("sa_config") or {}
    z.update(j=int(sa.get("indexer_num_heads", 0)), di=int(sa.get("indexer_head_dim", 0)))
    return z


def part_of(op_text: str, z: Dict[str, int]) -> str:
    """One of ``SPARSE_PARTS``, or what ``lm_trace.part_of`` says."""
    base = lm_trace.part_of(op_text, z)
    if base == "container" or not z.get("j"):
        return base
    if " conditional(" in op_text:
        return "container"
    head = op_text.split(", kind=", 1)[0]
    t = z["seq"]
    square = "[%d,%d]" % (t, t)
    if square not in head:
        return base
    custom = " custom-call(" in op_text
    shapes = lm_trace._shapes(head)
    if custom and any(s in ((z["j"], t, z["di"]), (t, z["j"], z["di"])) for s in shapes):
        return "indexer"
    heads = any(len(s) >= 2 and s[-2:] == (t, z["hd"]) for s in shapes) \
        and not any(z["d"] in s for s in shapes)
    writes_floats = "f32" + square in _OP_CALL.split(head, 1)[0]
    if custom and heads:
        return "align" if writes_floats else "attention"
    name = xtrace.short_name(op_text)
    return "align" if (writes_floats or "exponential" in name or "log" in name) else "select"


def part_seconds(run) -> Optional[Tuple[Dict[str, float], int]]:
    """(device seconds by part inside the step program, its whole executions)
    on device 0 of the traced part; None without a trace, a step in it, or a
    sparse layer in the configuration."""
    if run.trace is None or not (run.cell.config.get("sa_config") or {}):
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


def part_ms_per_step(run, part: str) -> Optional[float]:
    parts = part_seconds(run)
    if parts is None or not parts[0].get(part):
        return None
    return 1e3 * parts[0][part] / parts[1]


def counters(run) -> Optional[Dict[str, float]]:
    """Per step, over the ledger windows inside the measured window:
    ``scored`` and ``selected`` (query, key) pairs of all sparse layers
    together, and ``moe_pairs``. None where the program writes no such fields."""
    windows = [w for w in run.windows
               if "sparse_pairs_scored" in w and "sparse_pairs_selected" in w and "moe_pairs" in w]
    steps = sum(w["steps"] for w in windows)
    if not steps:
        return None
    return {
        "scored": sum(w["sparse_pairs_scored"] for w in windows) / steps,
        "selected": sum(w["sparse_pairs_selected"] for w in windows) / steps,
        "moe_pairs": sum(w["moe_pairs"] for w in windows) / steps,
    }
