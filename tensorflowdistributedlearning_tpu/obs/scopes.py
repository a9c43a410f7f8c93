"""Which scope each op of a compiled step program came from.

The program marks its layers with ``jax.named_scope``; a profiler trace's op
events carry an op's HLO text without its metadata, so the marks cannot be
read from a trace alone. They are in the compiled program, though: every
instruction of the optimized module keeps the ``op_name`` of the jaxpr
equation it was lowered from, and that one string tells the scope AND the
pass, for ops inside ``while`` bodies too::

    jit(step)/jvp(decoder/head_loss)/closed_call/while/body/dot_general          forward
    jit(step)/transpose(jvp(...))/checkpoint/rematted_computation/decoder/...    recompute
    jit(step)/transpose(jvp(...))/checkpoint/decoder/head_loss/while/...         backward

This module holds the three pieces that make a trace readable by scope:

- the **registry** (:data:`SCOPES`) and :func:`scope`, the one way the
  program opens a scope, so a typo cannot make a scope no reader knows;
- :func:`scope_of` and :func:`describe`: from an ``op_name`` to (chain of
  scopes, pass), and from a compiled program to one ``program_scopes``
  record — the instruction names are the names a trace's ``XLA Ops`` events
  start with;
- :class:`Program`, what the step factories return around their jitted
  step: it notices the calls that compiled, keeps their argument shapes and
  shardings, and describes each compiled program once, off the step path,
  when a ``Telemetry`` asks (:func:`drain`) — lowering the same function for
  the same arguments again hits jit's caches, so nothing is traced, lowered
  or compiled a second time.

:func:`by_scope` reduces (instruction, milliseconds) pairs over a record: the
continuous profiler's ``op_roofline.by_scope`` (``obs/profiler.py``).
``perfbench/scope_trace.py`` reads the same record for the benchmark's
metrics, with its own few lines: the yardstick imports nothing of the program.
"""

from __future__ import annotations

import logging
import re
import time
import weakref
from typing import (
    Any, Dict, Iterable, Iterator, List, NamedTuple, Optional, Sequence, Tuple,
)

logger = logging.getLogger(__name__)

PROGRAM_SCOPES_EVENT = "program_scopes"

# Every scope the program opens. A scope may lie inside another (the gate of
# a full-attention layer: ``decoder/attn_full`` then ``decoder/attn_gate``):
# an op belongs to the innermost and counts under every scope of its chain.
SCOPES: Tuple[str, ...] = (
    # train/state.py, parallel/zero.py, parallel/tensor.py: the update
    "optimizer",
    # train/step.py: the task's loss on the model's outputs
    "loss",
    # models/resnet.py, models/layers.py: the segmentation model
    "seg/backbone",
    "seg/aspp",
    "seg/decoder",
    # models/decoder.py, ops/sparse_attention.py: the decoder family
    "decoder/embed",
    "decoder/norm",
    "decoder/attn_sliding",
    "decoder/attn_full",
    "decoder/attn_sparse",
    "decoder/attn_sparse/indexer",
    "decoder/attn_sparse/select",
    "decoder/attn_sparse/attend",
    "decoder/attn_sparse/align",
    "decoder/attn_proj",
    "decoder/attn_gate",
    "decoder/moe/route",
    "decoder/moe/experts",
    "decoder/moe/shared",
    "decoder/mlp_dense",
    "decoder/head_loss",
    # train/pipeline_step.py: the pipelined steps' phases
    "pipeline/embed",
    "pipeline/entry",
    "pipeline/fill_drain",
    "pipeline/head",
)
_REGISTERED = frozenset(SCOPES)

PASSES: Tuple[str, ...] = ("forward", "backward", "recompute")

# an event line over this many bytes goes to a file beside the ledger
INLINE_LIMIT_BYTES = 256 * 1024


def scope(name: str):
    """``jax.named_scope(name)`` for a registered name. Trace-time metadata
    only: the optimized HLO is the same with and without it."""
    if name not in _REGISTERED:
        raise KeyError(f"{name!r} is not in obs.scopes.SCOPES; register it there")
    import jax

    return jax.named_scope(name)


# ---------------------------------------------------------------------------
# op_name -> (chain, pass)
# ---------------------------------------------------------------------------

# a scope occurs in a path bounded by "/", "(", ")" or ";" (XLA joins the
# names of merged instructions with ";") — it can sit inside "jvp(...)"; the
# longest registered name wins where two start at one place
_OCCURS = re.compile(
    r"(?<![^/(;])(" + "|".join(
        re.escape(s) for s in sorted(SCOPES, key=len, reverse=True)
    ) + r")(?![^/);])"
)


def scope_of(op_name: str) -> Tuple[Tuple[str, ...], str]:
    """``(chain, pass)``: the registered scopes that occur in ``op_name``,
    outermost first — the last is the innermost, the scope the op belongs
    to; an empty chain where none occurs — and ``recompute`` where the path
    holds ``rematted_computation``, else ``backward`` where it holds
    ``transpose(``, else ``forward``."""
    chain: List[str] = []
    for m in _OCCURS.finditer(op_name):
        name = m.group(1)
        if name in chain:
            chain.remove(name)
        chain.append(name)
    if "rematted_computation" in op_name:
        which = "recompute"
    elif "transpose(" in op_name:
        which = "backward"
    else:
        which = "forward"
    return tuple(chain), which


# ---------------------------------------------------------------------------
# compiled program -> record
# ---------------------------------------------------------------------------

_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation|false_computation)=%?([\w.\-]+)"
)
_CALLED_LIST = re.compile(r"\b(?:branch_computations|called_computations)=\{([^}]*)\}")
_REFERENCE = re.compile(r"%([\w.\-]+)")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")

# opcodes that run their called computations as ops of their own on the
# device's timeline; every other caller (a fusion, a reduce, a sort, a
# scatter, an all-reduce) runs its computation inside its own op
_CONTROL_FLOW = frozenset({"while", "conditional", "call", "async-start"})
# those of them whose own span on the timeline holds the called ops' spans
_CONTAINERS = frozenset({"while", "conditional", "call"})
# instructions that are no op on the device: they name a buffer or a part of
# one, and no event of a trace carries their name
_NO_OP = frozenset({"parameter", "constant", "tuple", "get-tuple-element", "bitcast"})


class _Instruction(NamedTuple):
    name: str
    opcode: str
    op_name: str
    called: Tuple[str, ...]  # computations
    operands: Tuple[str, ...]  # the instructions its operand list names


def _instruction(name: str, rest: str) -> _Instruction:
    op = _OPCODE.search(rest)
    meta = rest.rfind("metadata={")
    found = _OP_NAME.search(rest, meta) if meta >= 0 else None
    head = rest if meta < 0 else rest[:meta]
    called = _CALLED.findall(head)
    for names in _CALLED_LIST.findall(head):
        called.extend(n.strip().lstrip("%") for n in names.split(",") if n.strip())
    operands: Tuple[str, ...] = ()
    if op:
        # the operand list alone: attributes after it (control-predecessors,
        # called computations) name instructions that are no operands
        depth, end = 1, op.end()
        while end < len(head) and depth:
            depth += {"(": 1, ")": -1}.get(head[end], 0)
            end += 1
        operands = tuple(_REFERENCE.findall(head, op.end(), end))
    return _Instruction(
        name, op.group(1) if op else "", found.group(1) if found else "",
        tuple(called), operands,
    )


def _parse(text: str) -> Tuple[str, str, Dict[str, List[_Instruction]]]:
    """(module name, entry computation, {computation: its instructions in
    the order it runs them}) of an optimized HLO module's text."""
    module = ""
    entry = ""
    computations: Dict[str, List[_Instruction]] = {}
    current: Optional[List[_Instruction]] = None
    reading: Optional[List[str]] = None  # [name, text so far]

    def close():
        nonlocal reading
        if reading is not None:
            current.append(_instruction(*reading))
            reading = None

    for line in text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = computations.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            elif not module:
                m = _MODULE.match(line)
                if m:
                    module = m.group(1)
            continue
        if line.rstrip() == "}":
            close()
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if m:
            close()
            reading = [m.group(1), m.group(2)]
        elif reading is not None:
            # a kernel's backend_config holds line breaks: its metadata
            # follows on a later line
            reading[1] += " " + line
    return module, entry, computations


def _timeline(entry: str, computations: Dict[str, List[_Instruction]]) -> List[str]:
    """The computations whose instructions can appear as ops of their own on
    the device's timeline: the entry and what control flow calls from one."""
    seen, stack = [], [entry]
    while stack:
        name = stack.pop()
        if name in seen or name not in computations:
            continue
        seen.append(name)
        for inst in computations[name]:
            if inst.opcode in _CONTROL_FLOW:
                stack.extend(inst.called)
    return seen


def _fused_chains(name: str, computations, out: List[Tuple[Tuple[str, ...], str]]) -> None:
    """(chain, pass) of every scoped instruction a fused computation holds,
    nested calls too."""
    for inst in computations.get(name, ()):
        if inst.op_name:
            chain, which = scope_of(inst.op_name)
            if chain:
                out.append((chain, which))
        for inner in inst.called:
            _fused_chains(inner, computations, out)


def _place(computation: List[_Instruction], computations, root: str, mixed: Dict[str, List[str]]):
    """{instruction: (chain, pass)} of one timeline computation, and how many
    of them took a neighbour's.

    An instruction takes the scope of its own metadata. A fusion whose own
    names none takes the one scope its fused instructions name, if they name
    one; where they name several that do not lie inside one another, the
    fusion is listed under ``mixed``. What the compiler made to move a buffer
    — a copy, the two halves of an asynchronous copy or slice, a buffer it
    allocates, a gather it expanded: instructions whose ``op_name`` is empty
    or not rooted at the program — belongs to what produced the buffer, else
    to what reads it: with one operand it inherits (chain, pass) from that
    operand's instruction, if that has a scope (a tuple of many buffers says
    nothing about any one of them), else from the last instruction that
    reads it."""
    placed: Dict[str, Tuple[Tuple[str, ...], str]] = {}
    movable = set()
    for inst in computation:
        chain, which = scope_of(inst.op_name)
        if inst.opcode == "fusion":
            inside: List[Tuple[Tuple[str, ...], str]] = []
            for inner in inst.called:
                _fused_chains(inner, computations, inside)
            leaves = {c[-1] for c, _ in inside}
            leaves -= {s for c, _ in inside for s in c[:-1]}  # a scope around another
            if len(leaves) > 1:
                mixed[inst.name] = sorted(leaves)
            elif not chain and inside:
                chain, which = max(inside, key=lambda cw: len(cw[0]))
        placed[inst.name] = (chain, which)
        if not chain and not inst.op_name.startswith(root):
            movable.add(inst.name)
    for inst in computation:  # from what produced the buffer it alone reads
        if inst.name in movable and len(inst.operands) == 1:
            source = placed.get(inst.operands[0])
            if source and source[0]:
                placed[inst.name] = source
    for inst in reversed(computation):  # else from what reads it
        if placed[inst.name][0]:
            for operand in inst.operands:
                if operand in movable and not placed[operand][0]:
                    placed[operand] = placed[inst.name]
    inherited = sum(
        1 for inst in computation
        if inst.name in movable and inst.opcode not in _NO_OP and placed[inst.name][0]
    )
    return placed, inherited


def describe_text(text: str) -> Dict[str, Any]:
    """The ``program_scopes`` record of an optimized HLO module's text.

    ``ops`` holds every instruction of the entry computation and of what
    control flow calls from it (``while`` bodies and conditions, branches,
    called computations; not the insides of fused computations, and not what
    is no op on the device: parameters, constants, tuples and their elements,
    bitcasts), grouped: ``[chain, pass, [instruction names]]`` with indices
    into ``chains`` and ``passes``. A chain is a list of indices into
    ``scopes``, outermost first; chain 0 is the empty one. ``mixed`` maps a
    fusion whose fused instructions name several scopes to those scopes;
    ``containers`` lists the control flow whose called computations run as
    ops of their own inside its span (a reader skips them, or counts their
    bodies twice); ``inherited`` counts the instructions placed by a
    neighbour (see :func:`_place`)."""
    module, entry, computations = _parse(text)
    root = f"jit({module[4:]})" if module.startswith("jit_") else "jit("
    scope_index = {s: i for i, s in enumerate(SCOPES)}
    chains: Dict[Tuple[str, ...], int] = {(): 0}
    groups: Dict[Tuple[int, int], List[str]] = {}
    mixed: Dict[str, List[str]] = {}
    containers: List[str] = []
    inherited = count = 0
    for comp in _timeline(entry, computations):
        placed, n = _place(computations[comp], computations, root, mixed)
        inherited += n
        for inst in computations[comp]:
            if inst.opcode in _NO_OP:
                continue
            if inst.opcode in _CONTAINERS:
                containers.append(inst.name)
            chain, which = placed[inst.name]
            key = (chains.setdefault(chain, len(chains)), PASSES.index(which))
            groups.setdefault(key, []).append(inst.name)
            count += 1
    return {
        "program": module,
        "scopes": list(SCOPES),
        "passes": list(PASSES),
        "chains": [[scope_index[s] for s in chain] for chain in chains],
        "ops": [[c, p, names] for (c, p), names in sorted(groups.items())],
        "mixed": {name: sorted(scope_index[s] for s in found)
                  for name, found in mixed.items()},
        "containers": containers,
        "instructions": count,
        "inherited": inherited,
    }


def describe(compiled) -> Dict[str, Any]:
    """The ``program_scopes`` record of a compiled program (a
    ``jax.stages.Compiled``), with the seconds it took to make."""
    t0 = time.perf_counter()
    record = describe_text(compiled.as_text())
    record["seconds"] = round(time.perf_counter() - t0, 4)
    return record


# ---------------------------------------------------------------------------
# record + op times -> time by scope
# ---------------------------------------------------------------------------


def instruction_name(op_text: str) -> str:
    """An ``XLA Ops`` event's name is the op's HLO text, ``%name = ...``."""
    return op_text.split(" = ", 1)[0].strip().lstrip("%")


def is_container(name: str) -> bool:
    """Control flow by its default name, for an op no record lists: the ops
    of a ``while``'s or a ``conditional``'s body are events of their own
    inside it, so its own span would count them twice."""
    return name.startswith(("while", "conditional"))


def op_table(record: Dict[str, Any]) -> Dict[str, Tuple[Tuple[str, ...], str]]:
    """{instruction: (chain of scope names, pass)} of a record."""
    scopes, passes = record["scopes"], record["passes"]
    chains = [tuple(scopes[i] for i in chain) for chain in record["chains"]]
    return {
        name: (chains[c], passes[p]) for c, p, names in record["ops"] for name in names
    }


def by_scope(
    records: Sequence[Dict[str, Any]], op_ms: Iterable[Tuple[str, float]],
    steps: Optional[int] = None,
) -> Dict[str, Any]:
    """Milliseconds by innermost scope and pass for ``(instruction name,
    ms)`` pairs — a step where ``steps`` says how many the pairs cover —
    under the run's records (the newest that names an op decides):
    ``{"scopes": {scope: {pass: ms}}, "unnamed_ms", "unnamed_frac",
    "mixed_ms", "total_ms", "per_step", "programs"}``. An op with no
    registered scope, or with no entry in any record, is unnamed; containers
    are skipped."""
    table: Dict[str, Tuple[Tuple[str, ...], str]] = {}
    mixed, containers = set(), set()
    for record in records:
        table.update(op_table(record))
        mixed.update(record["mixed"])
        containers.update(record["containers"])
    out: Dict[str, Dict[str, float]] = {}
    unnamed = mixed_ms = total = 0.0
    for name, ms in op_ms:
        if name in containers or (name not in table and is_container(name)):
            continue
        total += ms
        chain, which = table.get(name, ((), ""))
        if not chain:
            unnamed += ms
            continue
        if name in mixed:
            mixed_ms += ms
        by_pass = out.setdefault(chain[-1], {})
        by_pass[which] = by_pass.get(which, 0.0) + ms
    per = float(steps) if steps else 1.0
    return {
        "programs": sorted({r["program"] for r in records}),
        "per_step": bool(steps),
        "scopes": {s: {p: round(v / per, 4) for p, v in sorted(by_pass.items())}
                   for s, by_pass in sorted(out.items())},
        "unnamed_ms": round(unnamed / per, 4),
        "unnamed_frac": round(unnamed / total, 4) if total else 0.0,
        "mixed_ms": round(mixed_ms / per, 4),
        "total_ms": round(total / per, 4),
    }


# ---------------------------------------------------------------------------
# the step factories' product
# ---------------------------------------------------------------------------

_PROGRAMS: "weakref.WeakSet[Program]" = weakref.WeakSet()


def _spec(x):
    """What jit keys a compiled program on, of one argument — readable from
    a donated (deleted) array too."""
    import jax

    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding if x.committed else None,
            weak_type=x.aval.weak_type,
        )
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


class Program:
    """A jitted step that can say what it compiled.

    Calls go straight to the jitted function; the only work added to a call
    is reading the size of jit's own cache before and after it. A call that
    grew the cache compiled a program: the arguments' shapes and shardings
    are kept, and :meth:`records` describes that program — once — when
    asked. Everything else (``lower``, ``trace``, ``clear_cache``...) is the
    jitted function's."""

    def __init__(self, jitted):
        self._jitted = jitted
        self._cache_size = jitted._cache_size
        self._pending: List[Any] = []
        self._records: List[Dict[str, Any]] = []
        self.called = False  # since the last drain
        _PROGRAMS.add(self)

    def __call__(self, *args):
        before = self._cache_size()
        out = self._jitted(*args)
        if self._cache_size() != before:
            import jax

            self._pending.append(jax.tree.map(_spec, args))
        self.called = True
        return out

    def __getattr__(self, name):
        return getattr(self._jitted, name)

    def records(self) -> List[Dict[str, Any]]:
        """One record per program this step has compiled, oldest first."""
        pending, self._pending = self._pending, []
        for specs in pending:
            t0 = time.perf_counter()
            try:
                record = describe(self._jitted.lower(*specs).compile())
            except Exception:  # noqa: BLE001 — a record is never worth a run
                logger.warning("could not describe a compiled step", exc_info=True)
                continue
            record["seconds"] = round(time.perf_counter() - t0, 4)
            self._records.append(record)
        return list(self._records)


def drain() -> Iterator[Dict[str, Any]]:
    """The records of every live step program called since the last drain
    (a program compiled in an earlier run of this process and called again
    in this one is described once and yielded to both)."""
    for program in list(_PROGRAMS):
        if program.called:
            program.called = False
            yield from program.records()
