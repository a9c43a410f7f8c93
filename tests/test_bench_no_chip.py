"""bench.py's headline measures a TPU or fails.

With no chip it prints an error line on stderr, prints NO number — nothing
under a TPU metric's name, no cached headline stamped stale, no CPU probe in
its place — and exits non-zero. With a chip, the child's record is the output,
unchanged. And MFU is priced from ONE peaks table keyed by the exact
``device_kind`` the chip reports, where an unknown TPU is an error.

No jax, no children: ``_run_child`` is monkeypatched and ``main()``'s stdout
is read directly.
"""

import json
import types

import pytest

import bench
from tensorflowdistributedlearning_tpu.utils import peaks

TPU_RECORD = {
    "metric": "resnet50_imagenet_train_throughput_per_chip",
    "value": 2412.66,
    "unit": "images/sec/chip",
    "vs_baseline": 6.702,
    "platform": "tpu",
    "device_kind": "TPU v5 lite",
    "mfu": 0.3509,
}


def _run_main(monkeypatch, capsys, child_result):
    calls = []

    def fake_run_child(timeout):
        calls.append(timeout)
        return child_result

    monkeypatch.setattr(bench, "_run_child", fake_run_child)
    monkeypatch.setattr(bench.sys, "argv", ["bench.py"])
    code = 0
    try:
        bench.main()
    except SystemExit as e:
        code = e.code
    captured = capsys.readouterr()
    return code, captured.out, captured.err, calls


def test_no_chip_is_an_error_line_and_a_nonzero_exit(monkeypatch, capsys):
    code, out, err, calls = _run_main(
        monkeypatch, capsys, {"__error__": "child rc=1: no TPU"}
    )
    assert code != 0
    assert out == ""  # no number, under any name
    assert "no TPU measurement" in err and "child rc=1" in err
    assert len(calls) == 1  # one child: no retry, no CPU probe after it


def test_a_child_that_ran_on_another_platform_is_no_measurement(
    monkeypatch, capsys
):
    cpu = dict(TPU_RECORD, platform="cpu", device_kind="cpu", value=30.29)
    code, out, err, _ = _run_main(monkeypatch, capsys, cpu)
    assert code != 0
    assert out == ""
    assert "platform='cpu'" in err


def test_a_tpu_run_is_the_output_unchanged(monkeypatch, capsys):
    code, out, _, calls = _run_main(monkeypatch, capsys, dict(TPU_RECORD))
    assert code == 0 and len(calls) == 1
    result = json.loads(out.strip().splitlines()[-1])
    assert result == TPU_RECORD
    assert "stale" not in result and "fallback_probe" not in result


def test_a_child_killed_mid_extras_keeps_its_headline_marked_partial(
    monkeypatch, capsys
):
    code, out, _, _ = _run_main(
        monkeypatch, capsys, dict(TPU_RECORD, partial=True)
    )
    assert code == 0
    assert json.loads(out)["partial"] is True


def test_nothing_writes_or_reads_a_cached_headline():
    """The stale carry-forward is gone with its file I/O."""
    for gone in ("_save_tpu_cache", "_load_tpu_cache", "TPU_CACHE_PATH",
                 "TPU_ATTEMPTS", "CPU_TIMEOUT_SECS"):
        assert not hasattr(bench, gone)


def _device(kind, platform):
    return types.SimpleNamespace(device_kind=kind, platform=platform)


def test_peak_comes_from_the_exact_device_kind():
    assert bench._peak_flops(_device("TPU v5 lite", "tpu")) == 197e12
    assert bench._peak_flops(_device("cpu", "cpu")) is None
    row = peaks.PEAKS["TPU v5 lite"]
    assert (row.bf16_flops, row.int8_ops, row.hbm_bytes_per_sec) == (
        197e12, 393e12, 819e9
    )
    assert row.source  # a peak without its source is a guess


@pytest.mark.parametrize("kind", ["TPU v5", "TPU v5e", "TPU v5p", "TPU v7x"])
def test_an_unknown_tpu_kind_raises_instead_of_matching_by_substring(kind):
    """The chip says 'TPU v5 lite'. A table matched by substring priced every
    other v5 part as one too — and 'v5e' never matched at all."""
    with pytest.raises(peaks.UnknownDeviceError, match="no published peaks"):
        bench._peak_flops(_device(kind, "tpu"))
    with pytest.raises(peaks.UnknownDeviceError):
        peaks.device_peaks(kind)  # a what-if topology's kind, no platform


def test_planner_prices_real_chips_from_the_table_and_cpu_as_whatif():
    from tensorflowdistributedlearning_tpu.parallel import planner

    chip = planner.Topology(
        n_devices=4, local_device_count=4, device_kind="TPU v5 lite"
    )
    assert chip.peak_flops() == 197e12
    assert chip.collective_latency_s() == planner.COLLECTIVE_LATENCY_S
    host = planner.Topology(n_devices=8, local_device_count=8)  # kind "cpu"
    assert host.peak_flops() == planner.CPU_WHATIF_PEAK_FLOPS
    assert host.collective_latency_s() == planner.COLLECTIVE_LATENCY_CPU_S
    unknown = planner.Topology(
        n_devices=4, local_device_count=4, device_kind="TPU v9"
    )
    with pytest.raises(peaks.UnknownDeviceError):
        unknown.peak_flops()
