"""The step's device time by the program's own scopes.

A program that marks its layers (``jax.named_scope``) writes, once for every
step program it compiles, a ``program_scopes`` record into its ledger: for
every instruction of the optimized module that can run as an op of its own,
the chain of scopes its metadata names and the pass it belongs to (forward,
backward, recompute). The instruction names are the names a trace's ``XLA
Ops`` events start with, so a trace is read by scope with no rule about
shapes: look each op up.

The record, as this file reads it (``docs/LEDGER_SCHEMA.md`` has the whole):
``program`` (the module's name as the ``XLA Modules`` line shows it),
``scopes`` and ``passes`` (names), ``chains`` (lists of indices into
``scopes``, outermost first; chain 0 is empty), ``ops``, a list of
``[chain, pass, [instruction names]]``, and ``containers`` (the ``while``s and
``conditional``s, whose spans hold their bodies' ops). An op belongs to the innermost scope
of its chain. A scope that is opened only inside another kind of block
carries that block's prefix (``decoder/attn_proj`` and ``decoder/attn_gate``
inside ``decoder/attn_full``), so a whole block is summed by prefix.

A program that writes no such record (a commit before the record existed)
gives ``None`` everywhere, and the metrics that read this are left out.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

from perfbench import xtrace

EVENT = "program_scopes"
UNNAMED = ""  # the scope of an op whose metadata names none, or that the record lacks

_last: Optional[Tuple[object, object]] = None  # (run, its reduction): seven readers share one


def record_of(run) -> Optional[Dict]:
    """The newest ``program_scopes`` record of the cell's step program that
    the run's ledger holds whole."""
    needle = run.cell.traffic["programs"]["step"]
    found = None
    for event in run.ledger:
        if event.get("event") == EVENT and needle in event.get("program", "") and "ops" in event:
            found = event
    return found


def op_table(record: Dict) -> Dict[str, Tuple[str, str]]:
    """{instruction: (innermost scope or UNNAMED, pass)}."""
    scopes, passes = record["scopes"], record["passes"]
    innermost = [scopes[chain[-1]] if chain else UNNAMED for chain in record["chains"]]
    return {name: (innermost[c], passes[p]) for c, p, names in record["ops"] for name in names}


def is_container(op_text: str, record: Dict) -> bool:
    """Control flow whose body's ops are events of their own inside its
    span: what the record lists under ``containers``, and a ``while`` or a
    ``conditional`` by its HLO text where a record lists none."""
    if xtrace.short_name(op_text).lstrip("%") in record.get("containers", ()):
        return True
    return " while(" in op_text or " conditional(" in op_text


def by_scope(run) -> Optional[Tuple[Dict[Tuple[str, str], float], int]]:
    """``({(scope, pass): device seconds}, whole executions)`` of the ops
    inside the step program on device 0 of the traced part. An op with no
    registered scope counts under ``(UNNAMED, its pass)``, one the record
    lacks under ``(UNNAMED, "")``; containers are skipped, so the values sum
    to the program's op time. ``None`` without a trace, a step in it, or a
    record."""
    global _last
    if _last is not None and _last[0] is run:
        return _last[1]
    out = None
    record = record_of(run) if run.trace is not None else None
    if record is not None:
        needle = run.cell.traffic["programs"]["step"]
        _, calls = xtrace.module_time_s(run.trace, needle)
        if calls:
            table = op_table(record)
            seconds: Dict[Tuple[str, str], float] = {}
            for text, _, dur in xtrace.ops_inside(run.trace, needle):
                if is_container(text, record):
                    continue
                key = table.get(xtrace.short_name(text).lstrip("%"), (UNNAMED, ""))
                seconds[key] = seconds.get(key, 0.0) + dur / 1e9
            out = (seconds, calls)
    _last = (run, out)
    return out


def ms_per_step(run, pick: Callable[[str, str], bool]) -> Optional[float]:
    """Device milliseconds a step of the ops whose (scope, pass) ``pick``
    takes; ``None`` where :func:`by_scope` has nothing or no op is taken."""
    reduced = by_scope(run)
    if reduced is None:
        return None
    seconds, calls = reduced
    taken = [v for (scope, which), v in seconds.items() if pick(scope, which)]
    return 1e3 * sum(taken) / calls if taken else None
