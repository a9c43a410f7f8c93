"""Reductions from a profiler trace to numbers.

A trace is held as plain data — planes of lines of ``[name, start_ns,
duration_ns]`` events — so the same reductions run on a live capture
(``load_xplane``, through ``jax.profiler.ProfileData``) and on the small
recorded trace the tests check them on (``from_json``).

Device planes are the ones named ``/device:TPU:<n>``; on each, the line
``XLA Ops`` holds one event per executed HLO op and ``XLA Modules`` one per
executed program. Lines nest (a module's ops lie inside it), so every
reduction names its line. Host spans the program writes through
``jax.profiler.TraceAnnotation`` (``obs/<span>``) sit on host-thread planes
and share the device planes' clock.
"""

from __future__ import annotations

import glob
import json
import os
import re
import statistics
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, float, float]  # name, start_ns, duration_ns

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "obs/"

COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "collective-broadcast",
)

# Op events carry their whole HLO text: ``%name = shape op(operands), kind=...``.
# Buckets go by the op's own name (first hit wins, in this order); what runs a
# convolution is told by its shapes, since XLA names most convolution fusions
# plainly ``%fusion.N``.
BUCKETS: Dict[str, Tuple[str, ...]] = {
    "collectives": COLLECTIVES,
    "copy": ("copy", "transpose", "bitcast", "slice", "concatenate", "pad", "reshape"),
    "elementwise_bn": ("fusion", "reduce", "select", "add", "multiply", "subtract", "divide",
                       "maximum", "rsqrt", "convert", "broadcast", "sort"),
}

_SHAPE = re.compile(r"\b(?:bf16|f16|f32|s8|u8|s32)\[(\d+(?:,\d+)*)\]")


def short_name(op_text: str) -> str:
    return op_text.split(" = ", 1)[0]


def is_conv_op(op_text: str, batch: int) -> bool:
    """An op runs a convolution if it reads or writes both a kernel-shaped
    tensor ([kh, kw, cin, cout] with kh, kw <= 3) and an activation
    ([batch, h, w, c]): forward, input-gradient and kernel-gradient
    convolutions all do, with whatever is fused around them; the optimizer's
    kernel-shaped updates and the elementwise passes over activations do
    not."""
    if short_name(op_text).lstrip("%").startswith("convolution"):
        return True
    kernel = activation = False
    for m in _SHAPE.finditer(op_text.split(", kind=", 1)[0]):
        dims = [int(d) for d in m.group(1).split(",")]
        if len(dims) == 4:
            if dims[0] <= 3 and dims[1] <= 3:
                kernel = True
            elif dims[0] == batch:
                activation = True
    return kernel and activation


def bucket_of(op_text: str, batch: int = 0) -> str:
    if batch and is_conv_op(op_text, batch):
        return "conv"
    lowered = short_name(op_text).lower()
    for bucket, needles in BUCKETS.items():
        if any(n in lowered for n in needles):
            return bucket
    return "other"


class Trace:
    def __init__(self, planes: List[dict]):
        self.planes = planes

    # -- construction --------------------------------------------------------

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        with open(path, encoding="utf-8") as f:
            return cls(json.load(f)["planes"])

    def to_json(self, path: str) -> None:
        with open(path, "w", encoding="utf-8") as f:
            json.dump({"planes": self.planes}, f)

    # -- access --------------------------------------------------------------

    def device_planes(self) -> List[dict]:
        found = [p for p in self.planes if re.fullmatch(r"/device:TPU:\d+", p["name"])]
        return sorted(found, key=lambda p: int(p["name"].rsplit(":", 1)[1]))

    @staticmethod
    def line(plane: dict, name: str) -> List[Event]:
        for ln in plane["lines"]:
            if ln["name"] == name:
                return ln["events"]
        return []

    def host_spans(self) -> List[Event]:
        spans = []
        for p in self.planes:
            if p["name"].startswith("/device:"):
                continue
            for ln in p["lines"]:
                spans.extend(e for e in ln["events"] if e[0].startswith(HOST_SPAN_PREFIX))
        return sorted(spans, key=lambda e: e[1])


def load_xplane(logdir: str, keep_host_prefix: str = HOST_SPAN_PREFIX) -> Optional[Trace]:
    """The newest ``*.xplane.pb`` under ``logdir`` as a Trace: device planes
    whole (ops and modules lines), host planes cut to the program's spans."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        return None
    data = ProfileData.from_file(files[-1])
    planes = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for ln in plane.lines:
            if device and ln.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [
                [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                for ev in ln.events
                if device or ev.name.startswith(keep_host_prefix)
            ]
            if events:
                lines.append({"name": ln.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return Trace(planes)


# -- reductions ----------------------------------------------------------------


def union_ns(events: Iterable[Event]) -> float:
    """Length of the union of the events' intervals."""
    total, end = 0.0, float("-inf")
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def busy_s(trace: Trace) -> float:
    """Seconds in which an op ran on the device, averaged over the devices."""
    planes = trace.device_planes()
    if not planes:
        return 0.0
    return sum(union_ns(Trace.line(p, OPS_LINE)) for p in planes) / len(planes) / 1e9


def fullest_busy_s(trace: Trace) -> float:
    planes = trace.device_planes()
    return max((union_ns(Trace.line(p, OPS_LINE)) for p in planes), default=0.0) / 1e9


def _whole_runs(modules: Iterable[Event], needle: str) -> List[Event]:
    """The executions of the programs whose name holds ``needle``, in order,
    without the ones the capture's edges clip: the first (it runs while the
    profiler starts) and the last where it was cut short — the capture stops
    at a step boundary of the host, with the next step's input program
    already on the device (a last run under half the median of the others)."""
    runs = sorted((e for e in modules if needle in e[0]), key=lambda e: e[1])[1:]
    if len(runs) >= 3 and runs[-1][2] < 0.5 * statistics.median(r[2] for r in runs[:-1]):
        runs = runs[:-1]
    return runs


def module_time_s(trace: Trace, needle: str, device: int = 0) -> Tuple[float, int]:
    """(seconds, executions) of the programs whose name holds ``needle`` on
    one device's modules line."""
    planes = trace.device_planes()
    if not planes:
        return 0.0, 0
    hits = _whole_runs(Trace.line(planes[device], MODULES_LINE), needle)
    return sum(e[2] for e in hits) / 1e9, len(hits)


def ops_inside(trace: Trace, needle: str, device: int = 0) -> List[Event]:
    """The ops that ran inside the programs whose name holds ``needle``."""
    planes = trace.device_planes()
    if not planes:
        return []
    spans = [
        (e[1], e[1] + e[2]) for e in _whole_runs(Trace.line(planes[device], MODULES_LINE), needle)
    ]
    if not spans:
        return []
    out, i = [], 0
    for ev in sorted(Trace.line(planes[device], OPS_LINE), key=lambda e: e[1]):
        while i < len(spans) and spans[i][1] <= ev[1]:
            i += 1
        if i < len(spans) and spans[i][0] <= ev[1] < spans[i][1]:
            out.append(ev)
    return out


def bucket_time_s(events: Iterable[Event], batch: int = 0) -> Dict[str, float]:
    """Seconds per bucket; ``batch`` is the per-chip batch the convolutions'
    activations carry."""
    out: Dict[str, float] = {}
    for name, _, dur in events:
        b = bucket_of(name, batch)
        out[b] = out.get(b, 0.0) + dur / 1e9
    return out


def exposed_collective_s(trace: Trace, device: int = 0) -> float:
    """Seconds of collective ops on one device during which no other op ran
    there."""
    planes = trace.device_planes()
    if not planes:
        return 0.0
    ops = Trace.line(planes[device], OPS_LINE)
    coll = [e for e in ops if bucket_of(e[0]) == "collectives"]
    rest = [e for e in ops if bucket_of(e[0]) != "collectives"]
    # exposed = |collectives| - |collectives ∩ rest| = |coll ∪ rest| - |rest|
    return (union_ns(coll + rest) - union_ns(rest)) / 1e9


def top_ops(trace: Trace, n: int = 10, device: int = 0) -> List[List]:
    planes = trace.device_planes()
    if not planes:
        return []
    totals: Dict[str, float] = {}
    for name, _, dur in Trace.line(planes[device], OPS_LINE):
        name = name[:120]  # the op's name and the head of its shapes
        totals[name] = totals.get(name, 0.0) + dur / 1e9
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10, device: int = 0) -> List[List]:
    """The longest gaps between ops on one device, each labelled by the host
    span of the program that covered most of it (``unattributed`` if none)."""
    planes = trace.device_planes()
    if not planes:
        return []
    ops = sorted(Trace.line(planes[device], OPS_LINE), key=lambda e: e[1])
    gaps, end = [], None
    for _, start, dur in ops:
        if end is not None and start > end:
            gaps.append((end, start))
        end = start + dur if end is None else max(end, start + dur)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    spans = trace.host_spans()
    out = []
    for g0, g1 in gaps:
        best, cover = "unattributed", 0.0
        for name, s, d in spans:
            if s >= g1:
                break
            overlap = min(g1, s + d) - max(g0, s)
            if overlap > cover:
                best, cover = name[len(HOST_SPAN_PREFIX):], overlap
        out.append([best, (g1 - g0) / 1e9])
    return out
