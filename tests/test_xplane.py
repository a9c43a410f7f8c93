"""XPlane reader tests against hand-encoded wire-format fixtures (no
TensorFlow: the parser IS the point — tensorflowdistributedlearning_tpu/utils/xplane.py reads
jax.profiler's *.xplane.pb without the TensorBoard dependency)."""

import os

import pytest

from tensorflowdistributedlearning_tpu.utils import xplane


def _varint(value: int) -> bytes:
    out = bytearray()
    while True:
        bits = value & 0x7F
        value >>= 7
        if value:
            out.append(bits | 0x80)
        else:
            out.append(bits)
            return bytes(out)


def _field(number: int, wire: int, payload: bytes) -> bytes:
    return _varint((number << 3) | wire) + payload


def _bytes_field(number: int, payload: bytes) -> bytes:
    return _field(number, 2, _varint(len(payload)) + payload)


def _varint_field(number: int, value: int) -> bytes:
    return _field(number, 0, _varint(value))


def _event(metadata_id: int, duration_ps: int, occurrences: int = 1) -> bytes:
    body = _varint_field(1, metadata_id) + _varint_field(3, duration_ps)
    if occurrences != 1:
        body += _varint_field(5, occurrences)
    return body


def _event_metadata_entry(meta_id: int, name: str) -> bytes:
    meta = _varint_field(1, meta_id) + _bytes_field(2, name.encode())
    entry = _varint_field(1, meta_id) + _bytes_field(2, meta)
    return entry


def make_xspace(tmp_path, plane_name="/device:TPU:0 (pid 1)", events=None,
                lines=None):
    """Serialize a one-plane XSpace. Either ``events`` = [(op, duration_ps, n)]
    for a single unnamed line, or ``lines`` = {line_name: [(op, dur, n)]}."""
    if lines is None:
        lines = {"": events or []}
    metadata = b""
    next_id = 1
    ids = {}
    for line_events in lines.values():
        for name, _, _ in line_events:
            if name not in ids:
                ids[name] = next_id
                metadata += _bytes_field(4, _event_metadata_entry(next_id, name))
                next_id += 1
    line_bufs = b""
    for line_name, line_events in lines.items():
        body = _varint_field(1, 7)
        if line_name:
            body += _bytes_field(2, line_name.encode())
        for name, dur, n in line_events:
            body += _bytes_field(4, _event(ids[name], dur, n))
        line_bufs += _bytes_field(3, body)
    plane = (
        _varint_field(1, 1)
        + _bytes_field(2, plane_name.encode())
        + metadata
        + line_bufs
    )
    space = _bytes_field(1, plane)
    run_dir = tmp_path / "plugins" / "profile" / "run1"
    os.makedirs(run_dir)
    path = run_dir / "host.xplane.pb"
    path.write_bytes(space)
    return str(tmp_path)


def test_op_breakdown_aggregates_and_sorts(tmp_path):
    logdir = make_xspace(
        tmp_path,
        events=[
            ("fusion.123", 2_000_000, 4),      # 0.002 ms
            ("convolution.5", 10_000_000, 2),  # 0.01 ms
        ],
    )
    rows = xplane.op_breakdown(logdir)
    assert [r.name for r in rows] == ["convolution.5", "fusion.123"]
    assert rows[0].total_ms == pytest.approx(0.01)
    assert rows[0].occurrences == 2
    assert rows[0].fraction == pytest.approx(10 / 12, abs=1e-3)


def test_plane_filter_excludes_host(tmp_path):
    logdir = make_xspace(
        tmp_path, plane_name="/host:CPU", events=[("python_thread", 5_000_000, 1)]
    )
    assert xplane.op_breakdown(logdir, plane_filter="") != []
    assert xplane.op_breakdown(logdir, plane_filter="TPU") == []
    assert xplane.plane_names(logdir) == ["/host:CPU"]


def test_grouped_breakdown_buckets():
    rows = [
        xplane.OpTime("convolution.9", 5.0, 1, 0.5),
        xplane.OpTime("loop_fusion.2", 3.0, 1, 0.3),
        xplane.OpTime("reduce.7", 1.0, 1, 0.1),
        xplane.OpTime("weird-op", 1.0, 1, 0.1),
    ]
    groups = xplane.grouped_breakdown(rows)
    assert groups["conv"] == 5.0
    assert groups["fusion(elementwise/bn)"] == 3.0
    assert groups["reduce"] == 1.0
    assert groups["other"] == 1.0


def test_grouped_breakdown_tags_quant_and_fused_kernels():
    """The Pallas quant/fused kernels show up in device traces under their
    kernel function names; the roofline classifier must fold the int8
    matmul/conv into the MXU compute buckets and the fused epilogue into the
    elementwise-fusion bucket, not ``other``."""
    rows = [
        xplane.OpTime("_qmm_kernel.4", 6.0, 2, 0.6),
        xplane.OpTime("_qconv_kernel.2", 3.0, 1, 0.3),
        xplane.OpTime("_fused_bias_act_kernel.3", 0.5, 1, 0.05),
    ]
    groups = xplane.grouped_breakdown(rows)
    assert groups["matmul"] == 6.0
    assert groups["conv"] == 3.0
    assert groups["fusion(elementwise/bn)"] == 0.5
    assert "other" not in groups
    assert xplane.classify_bucket("_qmm_kernel.4") == "matmul"
    assert xplane.classify_bucket("_qconv_kernel.2") == "conv"


def test_grouped_breakdown_splits_collectives_from_compute():
    """Cross-chip communication is its own bucket — all-reduce/all-gather/
    reduce-scatter/collective-permute time must NOT fold into the generic
    reduce bucket (the "slow network" half of straggler attribution)."""
    rows = [
        xplane.OpTime("all-reduce.1", 2.0, 4, 0.2),
        xplane.OpTime("all-gather.3", 1.0, 2, 0.1),
        xplane.OpTime("reduce-scatter.2", 1.5, 2, 0.15),
        xplane.OpTime("collective-permute.5", 0.5, 1, 0.05),
        xplane.OpTime("reduce.11", 1.0, 1, 0.1),
        xplane.OpTime("convolution.9", 4.0, 1, 0.4),
    ]
    groups = xplane.grouped_breakdown(rows)
    assert groups["collectives"] == 5.0
    assert groups["reduce"] == 1.0
    assert groups["conv"] == 4.0


def test_nested_lines_do_not_double_count(tmp_path):
    """Device planes nest timelines (Steps > XLA Modules > XLA Ops): the
    auto line filter must aggregate the op-level line ONLY, not re-count the
    whole step through its enclosing module/step events."""
    logdir = make_xspace(
        tmp_path,
        lines={
            "Steps": [("step_42", 12_000_000, 1)],
            "XLA Modules": [("jit_step", 12_000_000, 1)],
            "XLA Ops": [
                ("convolution.1", 8_000_000, 10),
                ("fusion.7", 4_000_000, 20),
            ],
        },
    )
    rows = xplane.op_breakdown(logdir)
    assert {r.name for r in rows} == {"convolution.1", "fusion.7"}
    assert sum(r.total_ms for r in rows) == pytest.approx(0.012)
    assert rows[0].fraction == pytest.approx(8 / 12, abs=1e-3)
    # explicit line filter overrides the auto selection
    module_rows = xplane.op_breakdown(logdir, line_filter="Modules")
    assert [r.name for r in module_rows] == ["jit_step"]


def test_host_planes_survive_unfiltered_aggregation(tmp_path):
    """plane_filter='' promises host threads included: the per-plane auto line
    filter must restrict only planes that HAVE an op-level line, not starve
    flat host planes because some other plane has one."""
    # two separate captures in one logdir: a device plane and a host plane
    make_xspace(
        tmp_path / "a",
        plane_name="/device:TPU:0",
        lines={
            "XLA Modules": [("jit_step", 9_000_000, 1)],
            "XLA Ops": [("convolution.1", 6_000_000, 3)],
        },
    )
    make_xspace(
        tmp_path / "b",
        plane_name="/host:CPU",
        lines={"thread/7": [("python_decode", 2_000_000, 5)]},
    )
    import shutil

    merged = tmp_path / "merged" / "plugins" / "profile" / "run1"
    os.makedirs(merged)
    shutil.copy(
        tmp_path / "a" / "plugins" / "profile" / "run1" / "host.xplane.pb",
        merged / "a.xplane.pb",
    )
    shutil.copy(
        tmp_path / "b" / "plugins" / "profile" / "run1" / "host.xplane.pb",
        merged / "b.xplane.pb",
    )
    rows = xplane.op_breakdown(str(tmp_path / "merged"), plane_filter="")
    names = {r.name for r in rows}
    assert "convolution.1" in names       # device op line kept
    assert "python_decode" in names       # host plane NOT starved
    assert "jit_step" not in names        # device module line still excluded


def test_missing_logdir_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        xplane.op_breakdown(str(tmp_path))


def test_user_substring_filter_skips_async_lines(tmp_path):
    """A user-supplied line_filter that substring-matches BOTH the op line and
    the overlapping 'Async XLA Ops' line (e.g. --line Ops) must not fold the
    async copy spans in through the side door — they overlap compute and
    corrupt every fraction (ADVICE round 5). Naming Async explicitly is the
    deliberate opt-in that still aggregates them."""
    logdir = make_xspace(
        tmp_path,
        lines={
            "XLA Ops": [("convolution.1", 8_000_000, 10)],
            "Async XLA Ops": [("copy-start.5", 56_000_000, 40)],
        },
    )
    # substring filter matching both lines: async skipped
    rows = xplane.op_breakdown(logdir, line_filter="Ops")
    assert [r.name for r in rows] == ["convolution.1"]
    assert rows[0].fraction == pytest.approx(1.0)
    # a filter that matches ONLY the async line: still skipped (it does not
    # name Async, so the user has not opted into overlap-corrupted sums)
    assert xplane.op_breakdown(logdir, line_filter="nc XLA") == []
    # naming Async explicitly is the opt-in
    async_rows = xplane.op_breakdown(logdir, line_filter="Async")
    assert [r.name for r in async_rows] == ["copy-start.5"]
    # exact-name behavior is unchanged
    exact = xplane.op_breakdown(logdir, line_filter="XLA Ops")
    assert [r.name for r in exact] == ["convolution.1"]
