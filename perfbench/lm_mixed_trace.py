"""Reductions a decoder cell of mixed layers adds to ``lm_trace.py``'s: which
part of the step an op belongs to, told from the shapes in its HLO text (the
trace's op events carry no scope), and the layers' counters from the ledger's
windows. With ``T`` the step's tokens, ``d`` the hidden width:

- **attention_window** / **attention_full**: a custom call that moves the
  queries of one key-value head, ``[.., heads a key-value head, T, head size]``,
  and none of the hidden width; which of the two by the heads — a window
  layer's group and a full layer's differ (8 and 6 in Laguna-XS.2's share);
- **experts**: a custom call that reads or writes the held experts' matrices
  ``[experts, d, expert width]`` (either order): ``gmm`` and ``tgmm``;
- **experts_other**: every other op over a segment of the sorted pair buffer
  (``flops_mixed.segment_rows`` rows), over the ``T x experts per token``
  pairs, sorted or still by token, over the router's ``[T, experts routed
  over]`` outputs — sort, gathers, activation, the rows' sum into their
  tokens — or over the held experts' matrices in the compute type alone (the
  zeroed sums of their gradients in the loop over segments; an op that also
  moves them in float32 is the optimizer's update or the cast it feeds, and is
  not the layer's);
- **shared_dense**: an op that moves an activation ``[.., T, width]`` of the
  dense layer's or the shared expert's width, or one of their matrices
  ``[d, width]`` beside a tensor with ``T`` in it (so not the optimizer's
  update of those matrices, whose shapes are all the matrix's own);
- **head_loss**, **other**, **container**: ``lm_trace.part_of``'s.

The shared expert's width may equal the routed experts' (512 in Laguna-XS.2):
the routed experts' activations have a segment's rows, never ``T``.
"""

from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

from perfbench import flops_mixed, lm_trace, xtrace


def sizes(cell) -> Dict[str, int]:
    cfg = cell.config
    z = lm_trace.sizes(cell)
    hkv = cfg["num_key_value_heads"]
    groups = {kind: sorted({h // hkv for h in flops_mixed.heads_of_kind(cfg, kind)})
              for kind in set(flops_mixed.layer_kinds(cfg))}
    z.update(
        segment=flops_mixed.segment_rows(cfg, cell.traffic),
        f_dense=int(cfg.get("intermediate_size") or 0),
        f_shared=int(cfg.get("shared_expert_intermediate_size") or 0),
        group_window=groups.get("sliding_attention", []),
        group_full=groups.get("full_attention", []),
    )
    return z


def _moves_matrices_in_compute_type_alone(head: str, z: Dict[str, int]) -> bool:
    e, d, f = z["e"], z["d"], z["f"]
    shape = r"\[(?:%d,%d,%d|%d,%d,%d)\]" % (e, d, f, e, f, d)
    return bool(re.search(r"\bbf16" + shape, head)) and not re.search(r"\bf32" + shape, head)


def part_of(op_text: str, z: Dict[str, int]) -> str:
    base = lm_trace.part_of(op_text, z)
    if base in ("container", "moe_experts"):
        return "experts" if base == "moe_experts" else base
    shapes = lm_trace._shapes(op_text.split(", kind=", 1)[0])
    t, d = z["tokens"], z["d"]
    if base == "attention":
        for name in ("window", "full"):
            if any(len(s) >= 3 and s[-2:] == (z["seq"], z["hd"]) and s[-3] in z["group_" + name]
                   for s in shapes):
                return "attention_" + name
        return "other"
    if base == "moe_other" or any(s and s[0] == z["segment"] for s in shapes) \
            or _moves_matrices_in_compute_type_alone(op_text.split(", kind=", 1)[0], z):
        return "experts_other"
    if base == "head_loss":
        return base
    widths = [w for w in (z["f_dense"], z["f_shared"]) if w]
    activation = any(len(s) >= 2 and s[-1] in widths and t in s[:-1] for s in shapes)
    matrix = any(s in ((d, w), (w, d)) for s in shapes for w in widths)
    if activation or (matrix and any(t in s for s in shapes)):
        return "shared_dense"
    return "other"


def part_seconds(run) -> Optional[Tuple[Dict[str, float], int]]:
    """(device seconds by part inside the step program, its whole executions)
    on device 0 of the traced part; None without a trace, a step in it, or head
    counts by layer in the configuration. Worked out once a run: seven readers
    ask, and a trace holds some 10^5 ops."""
    if run.trace is None or not run.cell.config.get("num_attention_heads_per_layer"):
        return None
    kept = getattr(run, "_mixed_part_seconds", None)
    if kept is not None and kept[0] is run.trace:
        return kept[1]
    needle = run.cell.traffic["programs"]["step"]
    _, calls = xtrace.module_time_s(run.trace, needle)
    z = sizes(run.cell)
    out: Dict[str, float] = {}
    for name, _, dur in xtrace.ops_inside(run.trace, needle) if calls else ():
        part = part_of(name, z)
        if part != "container":
            out[part] = out.get(part, 0.0) + dur / 1e9
    found = (out, calls) if calls else None
    run._mixed_part_seconds = (run.trace, found)
    return found


def part_ms_per_step(run, *parts: str) -> Optional[float]:
    seen = part_seconds(run)
    if seen is None:
        return None
    total = sum(seen[0].get(p, 0.0) for p in parts)
    return 1e3 * total / seen[1] if total else None


def counters(run) -> Optional[Dict[str, object]]:
    """``lm_trace.counters`` (per step ``moe_pairs`` of all sparse layers,
    ``keys_per_query`` by layer type) with, where the program writes them, the
    row tiles the grouped products visit a step (``tile_visits``) and the head
    gates' mean by layer type (``gate_mean``), both weighted by the windows'
    steps. None where the program writes no decoder fields."""
    seen = lm_trace.counters(run)
    if seen is None:
        return None
    windows = [w for w in run.windows if "moe_pairs" in w and "attn_keys_per_query" in w]
    steps = sum(w["steps"] for w in windows)
    if all("moe_tile_visits" in w for w in windows):
        seen["tile_visits"] = sum(w["moe_tile_visits"] * w["steps"] for w in windows) / steps
    if all("attn_gate_mean" in w for w in windows):
        seen["gate_mean"] = {
            kind: sum(w["attn_gate_mean"][kind] * w["steps"] for w in windows) / steps
            for kind in windows[0]["attn_gate_mean"]}
    return seen
