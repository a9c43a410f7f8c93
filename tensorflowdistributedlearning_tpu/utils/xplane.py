"""XPlane profile reader: per-op device-time breakdown without TensorFlow.

``jax.profiler.start_trace`` writes its device timeline as an ``XSpace``
protocol buffer (``*.xplane.pb``). The stock consumer is TensorBoard's profile
plugin — a TensorFlow dependency this framework doesn't carry. This module
reads the wire format directly (protobuf is length-delimited tag/value pairs;
the XPlane schema is public: tensorflow/tsl ``profiler/protos/xplane.proto``)
and aggregates per-op device time, so "where does the step time go" is
answerable on any machine the trace was captured on.

The reference had no profiler story at all (SURVEY §5.1); TensorBoard-free
trace reading is the subsystem that closes the loop the other way — not just
writing traces (``utils.profiling.trace``) but deciding from them.

Usage::

    from tensorflowdistributedlearning_tpu.utils import profiling, xplane
    with profiling.trace(logdir):
        run_steps()
    for row in xplane.op_breakdown(logdir)[:20]:
        print(row.name, row.total_ms, row.occurrences)

or ``python -m tensorflowdistributedlearning_tpu.utils.xplane <logdir>``.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterator, List, Optional, Tuple

# -- protobuf wire-format scanner -------------------------------------------

_WIRE_VARINT = 0
_WIRE_FIXED64 = 1
_WIRE_BYTES = 2
_WIRE_FIXED32 = 5


def _read_varint(buf: bytes, pos: int) -> Tuple[int, int]:
    result = 0
    shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7
        if shift >= 70:  # negative int64s legitimately take 10 bytes
            raise ValueError("varint overflow (corrupt protobuf)")


def _fields(buf) -> Iterator[Tuple[int, int, object]]:
    """Yield (field_number, wire_type, value) over a serialized message
    (``bytes`` or ``memoryview``). BYTES fields yield memoryview slices, and
    nested messages feed them straight back in — zero-copy end to end (traces
    reach 100s of MB)."""
    view = memoryview(buf)
    pos = 0
    end = len(buf)
    while pos < end:
        key, pos = _read_varint(buf, pos)
        field, wire = key >> 3, key & 7
        if wire == _WIRE_VARINT:
            value, pos = _read_varint(buf, pos)
        elif wire == _WIRE_BYTES:
            length, pos = _read_varint(buf, pos)
            value = view[pos : pos + length]
            pos += length
        elif wire == _WIRE_FIXED64:
            value = int.from_bytes(view[pos : pos + 8], "little")
            pos += 8
        elif wire == _WIRE_FIXED32:
            value = int.from_bytes(view[pos : pos + 4], "little")
            pos += 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield field, wire, value


# -- XPlane schema (field numbers from tsl's xplane.proto) -------------------

# XSpace: planes = 1
# XPlane: id=1, name=2, lines=3, event_metadata=4 (map), stat_metadata=5 (map)
# XLine:  id=1, name=2, timestamp_ns=3, events=4
# XEvent: metadata_id=1, offset_ps=2, duration_ps=3, stats=4, num_occurrences=5
# XEventMetadata: id=1, name=2
# map entry: key=1, value=2


def _parse_event_metadata(plane_buf) -> Dict[int, str]:
    names: Dict[int, str] = {}
    for field, _, value in _fields(plane_buf):
        if field != 4:
            continue
        key = None
        meta_name = ""
        for f2, _, v2 in _fields(value):
            if f2 == 1:
                key = v2
            elif f2 == 2:
                meta_id = None
                for f3, _, v3 in _fields(v2):
                    if f3 == 1:
                        meta_id = v3
                    elif f3 == 2:
                        meta_name = bytes(v3).decode("utf-8", "replace")
                if key is None:
                    key = meta_id
        if key is not None:
            names[key] = meta_name
    return names


@dataclasses.dataclass
class OpTime:
    name: str
    total_ms: float
    occurrences: int
    # share of the aggregated op time across every matched plane/file (on a
    # multi-chip capture that is fleet time, not one chip's step time)
    fraction: float


@dataclasses.dataclass
class PlaneBreakdown:
    plane: str
    total_ms: float
    ops: List[OpTime]


def _plane_name(plane_buf) -> str:
    """The plane's name alone — a cheap top-level scan (length-delimited
    payloads are skipped, not decoded) so callers can reject planes by name
    without paying for a full :func:`_parse_plane`."""
    for field, _, value in _fields(plane_buf):
        if field == 2:
            return bytes(value).decode("utf-8", "replace")
    return ""


def _parse_plane(
    plane_buf,
) -> Tuple[str, Dict[str, Dict[str, List[float]]]]:
    """(plane_name, {line_name: {event_name: [duration_ms, occurrences]}}).

    Lines stay SEPARATE: a device plane carries hierarchical timelines
    ("Steps" > "XLA Modules" > "XLA Ops") whose events nest — summing across
    lines would double-count every op inside its module inside its step."""
    name = ""
    metadata = _parse_event_metadata(plane_buf)
    lines: Dict[str, Dict[str, List[float]]] = {}
    for field, _, value in _fields(plane_buf):
        if field == 2:
            name = bytes(value).decode("utf-8", "replace")
        elif field == 3:  # XLine — one pass; field order is not guaranteed,
            # so aggregate locally and resolve the line name at the end
            line_name = ""
            display_name = ""
            line_agg: Dict[str, List[float]] = {}
            for f2, _, v2 in _fields(value):
                if f2 == 2:
                    line_name = bytes(v2).decode("utf-8", "replace")
                elif f2 == 11:
                    display_name = bytes(v2).decode("utf-8", "replace")
                elif f2 == 4:  # XEvent
                    meta_id = 0
                    dur_ps = 0
                    occurrences = 1
                    for f3, _, v3 in _fields(v2):
                        if f3 == 1:
                            meta_id = v3
                        elif f3 == 3:
                            dur_ps = v3
                        elif f3 == 5:
                            occurrences = v3
                    op = metadata.get(meta_id, f"#{meta_id}")
                    entry = line_agg.setdefault(op, [0.0, 0])
                    entry[0] += dur_ps / 1e9  # ps -> ms
                    entry[1] += occurrences
            agg = lines.setdefault(line_name or display_name, {})
            for op, (ms, cnt) in line_agg.items():
                entry = agg.setdefault(op, [0.0, 0])
                entry[0] += ms
                entry[1] += cnt
    return name, lines


def find_xplane_files(logdir: str) -> List[str]:
    """All ``*.xplane.pb`` under ``logdir`` (jax writes
    ``plugins/profile/<run>/<host>.xplane.pb``)."""
    return sorted(
        glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    )


def op_breakdown(
    logdir: str,
    *,
    plane_filter: str = "TPU",
    line_filter: Optional[str] = None,
    top: Optional[int] = None,
) -> List[OpTime]:
    """Aggregate per-op device time across every matching device plane under
    ``logdir``, sorted by total time descending.

    ``plane_filter`` substring-matches plane names (``"/device:TPU:0"`` etc.);
    pass ``""`` to aggregate every plane (host threads included).

    ``line_filter`` substring-matches timeline (XLine) names within a plane.
    Device planes nest their timelines ("Steps" > "XLA Modules" > "XLA Ops"),
    so summing every line would count each op again inside its module and its
    step. The default (None) auto-selects PER PLANE: a plane with an
    "XLA Ops" line contributes only its op-level lines; planes without one
    (host planes — flat thread lines) contribute every line. ``fraction`` is
    each op's share of the aggregated time — with op-level lines and one
    traced step per capture this reads directly as "share of the step".

    Truncated/partially-written plane files (a capture torn by SIGKILL) are
    SKIPPED, not fatal — see :func:`op_breakdown_with_errors` for the count."""
    rows, _ = op_breakdown_with_errors(
        logdir, plane_filter=plane_filter, line_filter=line_filter, top=top
    )
    return rows


def op_breakdown_with_errors(
    logdir: str,
    *,
    plane_filter: str = "TPU",
    line_filter: Optional[str] = None,
    top: Optional[int] = None,
) -> Tuple[List[OpTime], int]:
    """:func:`op_breakdown` plus the count of plane files skipped as
    corrupt/truncated. A torn capture (profiler killed mid-write — SIGKILL,
    OOM, preemption) leaves a partial ``*.xplane.pb`` whose wire scan raises;
    one torn file must not take down a whole-workdir report, so each file
    parses independently, bad ones are counted and skipped with a warning,
    and the good ones still aggregate. Raises FileNotFoundError only when NO
    plane file exists at all; a logdir where every file is torn returns
    ``([], n_skipped)``."""
    import logging as _logging

    paths = find_xplane_files(logdir)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {logdir}")
    plane_lines: List[Dict[str, Dict[str, List[float]]]] = []
    skipped = 0
    for path in paths:
        try:
            with open(path, "rb") as f:
                space = f.read()
            file_planes = []
            for field, _, value in _fields(space):
                if field != 1:
                    continue
                # resolve the name from the plane's top-level fields before
                # parsing the body: payloads are length-skipped memoryviews,
                # so rejecting a plane (host threads on TPU, the event-less
                # /host:metadata plane everywhere) costs O(#fields), not
                # O(bytes) — on a 4 MB CPU capture that is ~40% of the parse
                if plane_filter and plane_filter not in _plane_name(value):
                    continue
                name, lines = _parse_plane(value)
                if plane_filter and plane_filter not in name:
                    continue
                file_planes.append(lines)
            # all-or-nothing per file: a plane scanned before the tear must
            # not half-contribute a file the count reports as skipped
            plane_lines.extend(file_planes)
        except (ValueError, IndexError, OSError) as e:
            # IndexError: _read_varint ran off the end of a truncated buffer;
            # ValueError: overflow / unsupported wire type mid-garbage
            skipped += 1
            _logging.getLogger(__name__).warning(
                "skipping truncated/corrupt plane file %s: %s", path, e
            )
    agg: Dict[str, List[float]] = {}
    for lines in plane_lines:
        effective_filter = line_filter
        auto_selected = False
        if effective_filter is None and any("XLA Ops" in line for line in lines):
            effective_filter = "XLA Ops"
            auto_selected = True
        # TPU device planes carry BOTH an 'XLA Ops' line (the serialized
        # TensorCore timeline — sums to the step wall) and an 'Async XLA
        # Ops' line (copy-start/done spans that OVERLAP compute; on the
        # 2026-08-01 v5e capture it summed to 7x the wall). A substring
        # match would fold both and invent a giant copy bucket, so whenever
        # the requested filter names an existing line EXACTLY — auto-selected
        # or user-supplied — only that line contributes; and in substring
        # mode Async timelines are skipped outright — auto-selected OR
        # user-supplied (a user filter like "XLA" or "Ops" must not fold the
        # overlapping async spans in through the side door) — UNLESS the
        # user's filter itself names Async, which is the one way to opt into
        # aggregating those spans deliberately.
        exact_only = effective_filter is not None and any(
            line == effective_filter for line in lines
        )
        skip_async = auto_selected or (
            effective_filter is not None and "Async" not in effective_filter
        )
        for line_name, line_agg in lines.items():
            if exact_only:
                if line_name != effective_filter:
                    continue
            elif effective_filter and effective_filter not in line_name:
                continue
            elif skip_async and "Async" in line_name:
                continue
            for op, (ms, cnt) in line_agg.items():
                entry = agg.setdefault(op, [0.0, 0])
                entry[0] += ms
                entry[1] += cnt
    total = sum(ms for ms, _ in agg.values()) or 1.0
    rows = [
        OpTime(name=op, total_ms=round(ms, 4), occurrences=int(cnt),
               fraction=round(ms / total, 4))
        for op, (ms, cnt) in agg.items()
    ]
    rows.sort(key=lambda r: -r.total_ms)
    return (rows[:top] if top else rows), skipped


def plane_names(logdir: str) -> List[str]:
    """Every plane name in the capture (pick the device plane to filter on)."""
    names = []
    for path in find_xplane_files(logdir):
        try:
            with open(path, "rb") as f:
                space = f.read()
            for field, _, value in _fields(space):
                if field == 1:
                    for f2, _, v2 in _fields(value):
                        if f2 == 2:
                            names.append(bytes(v2).decode("utf-8", "replace"))
                            break
        except (ValueError, IndexError, OSError):
            continue  # torn capture — same stance as op_breakdown
    return names


# the default grouped_breakdown buckets, public because the roofline
# classifier (obs/profiler.py) keys its compute/HBM/collective split on the
# SAME bucket names — one bucketing, two consumers
DEFAULT_GROUPS: Dict[str, Tuple[str, ...]] = {
    # Pallas kernels surface in device traces under their kernel function
    # name ("_qmm_kernel", "_qconv_kernel", ...). The int8 matmul/conv run
    # the MXU just like their XLA counterparts, so they must land in the
    # compute buckets the roofline classifier keys on; "qconv" is caught by
    # the "conv" needle, "qmm" needs its own. The fused bias+act epilogue
    # is single-HBM-pass elementwise work — same class as XLA fusions.
    "conv": ("convolution", "conv"),
    "matmul": ("dot", "einsum", "qmm"),
    "fusion(elementwise/bn)": ("fusion", "fused_bias_act"),
    "collectives": (
        "all-reduce",
        "all-gather",
        "reduce-scatter",
        "collective-permute",
        "all-to-all",
        "collective-broadcast",
        "ragged-all-to-all",
    ),
    "reduce": ("reduce",),
    "copy/transpose": ("copy", "transpose", "bitcast"),
    "infeed/outfeed": ("infeed", "outfeed"),
}


def classify_bucket(op_name: str) -> str:
    """The :data:`DEFAULT_GROUPS` bucket ``op_name`` falls into (first hit in
    insertion order, ``"other"`` when none matches) — per-op form of
    :func:`grouped_breakdown`."""
    lowered = op_name.lower()
    for bucket, needles in DEFAULT_GROUPS.items():
        if any(n in lowered for n in needles):
            return bucket
    return "other"


def grouped_breakdown(
    rows: List[OpTime], groups: Optional[Dict[str, Tuple[str, ...]]] = None
) -> Dict[str, float]:
    """Fold an op breakdown into coarse buckets by substring match (first hit
    wins, in insertion order) — the "where does the time go" summary.

    Cross-chip/cross-host collectives get their OWN bucket, listed before the
    generic ``reduce`` needles so all-reduce/all-gather/reduce-scatter/
    collective-permute/all-to-all time is separated from compute: on a
    multi-host capture a fat ``collectives`` bucket with healthy per-host
    step times reads as a slow NETWORK, where a straggling host shows up in
    the fleet report's per-host skew instead (obs/fleet.py)."""
    groups = groups or DEFAULT_GROUPS
    out = {k: 0.0 for k in groups}
    out["other"] = 0.0
    for row in rows:
        lowered = row.name.lower()
        for bucket, needles in groups.items():
            if any(n in lowered for n in needles):
                out[bucket] += row.total_ms
                break
        else:
            out["other"] += row.total_ms
    return {k: round(v, 3) for k, v in out.items() if v}


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    import json

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("logdir")
    parser.add_argument("--plane", default="TPU", help="plane-name substring filter")
    parser.add_argument(
        "--line", default=None,
        help="timeline-name substring filter (default: auto — op-level lines "
        "only when the plane has an 'XLA Ops' line)",
    )
    parser.add_argument("--top", type=int, default=25)
    parser.add_argument("--json", action="store_true", help="machine-readable output")
    args = parser.parse_args(argv)

    rows = op_breakdown(args.logdir, plane_filter=args.plane, line_filter=args.line)
    if args.json:
        print(json.dumps({
            "planes": plane_names(args.logdir),
            "groups": grouped_breakdown(rows),
            "top_ops": [dataclasses.asdict(r) for r in rows[: args.top]],
        }))
        return 0
    print("planes:", ", ".join(plane_names(args.logdir)))
    print("\nbuckets (ms):")
    for bucket, ms in grouped_breakdown(rows).items():
        print(f"  {bucket:<24} {ms:>10.3f}")
    print(f"\ntop {args.top} ops:")
    for row in rows[: args.top]:
        print(f"  {row.total_ms:>10.3f} ms  x{row.occurrences:<6} "
              f"{row.fraction:>6.1%}  {row.name}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
