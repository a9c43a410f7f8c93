"""Continuous-profiling subsystem tests (obs/profiler.py + the planner's
measured-cost loop): degraded paths first — CPU hosts must OMIT MFU rather
than fabricate 0/0, empty/missing capture logdirs and torn plane files must
degrade to counted warnings, alert-triggered postmortems must rate-limit,
capture-during-capture must be refused, and a constructed-but-disabled
profiler must leave the ledger event stream untouched — then the headline
drill: a real ``fit_preset`` run with ``profile_every_windows`` set ledgers
an ``op_roofline`` whose MFU agrees with the report's goodput MFU within
10%, and ``plan --measured-costs-from`` re-scores candidates from it with
measured provenance."""

import json
import os
import time

import pytest

from tensorflowdistributedlearning_tpu import obs
from tensorflowdistributedlearning_tpu.obs import profiler as profiler_lib
from tensorflowdistributedlearning_tpu.obs.health import HealthMonitor
from tensorflowdistributedlearning_tpu.utils import xplane


# -- synthetic xplane wire bytes ---------------------------------------------
# Hand-rolled protobuf wire encoding matching the field numbers
# utils/xplane.py scans (XSpace.planes=1; XPlane.name=2, lines=3,
# event_metadata=4; XLine.name=2, events=4; XEvent.metadata_id=1,
# duration_ps=3, num_occurrences=5) — lets every state-machine test run
# without paying for a real jax.profiler trace.


def _vint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _varint_field(field: int, value: int) -> bytes:
    return _vint(field << 3) + _vint(value)


def _bytes_field(field: int, payload: bytes) -> bytes:
    return _vint((field << 3) | 2) + _vint(len(payload)) + payload


def _xspace_bytes(
    plane_name: str = "/host:CPU",
    line_name: str = "XLA Ops",
    events=(("fusion.1", 2.0, 1),),
) -> bytes:
    meta = b""
    line_events = b""
    for i, (name, dur_ms, occ) in enumerate(events, start=1):
        meta += _bytes_field(
            4,
            _varint_field(1, i)
            + _bytes_field(
                2, _varint_field(1, i) + _bytes_field(2, name.encode())
            ),
        )
        line_events += _bytes_field(
            4,
            _varint_field(1, i)
            + _varint_field(3, int(dur_ms * 1e9))  # ps
            + _varint_field(5, occ),
        )
    line = _bytes_field(2, line_name.encode()) + line_events
    plane = _bytes_field(2, plane_name.encode()) + meta + _bytes_field(3, line)
    return _bytes_field(1, plane)


def _write_xspace(dirpath, name="host.xplane.pb", **kw) -> str:
    os.makedirs(dirpath, exist_ok=True)
    path = os.path.join(dirpath, name)
    with open(path, "wb") as f:
        f.write(_xspace_bytes(**kw))
    return path


class _FakeJaxProfiler:
    """Monkeypatched stand-in for jax.profiler.start/stop_trace: records the
    requested logdir and, on stop, writes a small synthetic plane file there
    so the parse/ledger path runs for real."""

    def __init__(self, write_planes: bool = True):
        self.write_planes = write_planes
        self.dirs = []
        self._current = None

    def start_trace(self, logdir):
        self._current = logdir
        self.dirs.append(logdir)

    def stop_trace(self):
        if self.write_planes and self._current:
            _write_xspace(
                self._current,
                events=(
                    ("dot.1", 6.0, 3),  # compute class
                    ("all-reduce.2", 3.0, 3),  # collective class
                    ("copy.3", 1.0, 3),  # hbm class
                ),
            )
        self._current = None


@pytest.fixture
def fake_tracer(monkeypatch):
    import jax

    fake = _FakeJaxProfiler()
    monkeypatch.setattr(jax.profiler, "start_trace", fake.start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", fake.stop_trace)
    return fake


# -- xplane degraded paths ---------------------------------------------------


def test_xplane_synthetic_roundtrip(tmp_path):
    _write_xspace(str(tmp_path), events=(("matmul.5", 4.0, 2),
                                         ("all-reduce.1", 1.0, 2)))
    rows, skipped = xplane.op_breakdown_with_errors(
        str(tmp_path), plane_filter="/host:CPU"
    )
    assert skipped == 0
    assert [r.name for r in rows] == ["matmul.5", "all-reduce.1"]
    assert rows[0].total_ms == pytest.approx(4.0)
    assert rows[0].occurrences == 2


def test_torn_plane_file_skipped_with_count(tmp_path):
    _write_xspace(str(tmp_path), name="good.xplane.pb")
    # 0x80 continuation bytes forever: _read_varint runs off the buffer end
    with open(tmp_path / "torn.xplane.pb", "wb") as f:
        f.write(b"\x80" * 64)
    rows, skipped = xplane.op_breakdown_with_errors(
        str(tmp_path), plane_filter="/host:CPU"
    )
    assert skipped == 1
    assert [r.name for r in rows] == ["fusion.1"]  # the good file survives


def test_all_torn_returns_empty_not_raise(tmp_path):
    with open(tmp_path / "a.xplane.pb", "wb") as f:
        f.write(b"\x80" * 16)
    with open(tmp_path / "b.xplane.pb", "wb") as f:
        f.write(b"\xff" * 16)
    rows, skipped = xplane.op_breakdown_with_errors(str(tmp_path))
    assert rows == [] and skipped == 2


def test_missing_and_empty_logdir_raise_filenotfound(tmp_path):
    with pytest.raises(FileNotFoundError):
        xplane.op_breakdown_with_errors(str(tmp_path / "nope"))
    with pytest.raises(FileNotFoundError):
        xplane.op_breakdown_with_errors(str(tmp_path))  # exists, no planes


def test_plane_name_prefilter_skips_nonmatching(tmp_path):
    _write_xspace(str(tmp_path), plane_name="/host:metadata",
                  events=(("noise", 9.0, 1),))
    _write_xspace(str(tmp_path), name="dev.xplane.pb",
                  plane_name="/device:TPU:0", events=(("op.1", 2.0, 1),))
    rows, _ = xplane.op_breakdown_with_errors(str(tmp_path),
                                              plane_filter="TPU")
    assert [r.name for r in rows] == ["op.1"]


# -- MFU pricing: absent beats fabricated ------------------------------------


def _drive_windows(tel, n_windows=1, step_s=0.002, steps_per_window=2,
                   dirty=False):
    step = 0
    for _ in range(n_windows):
        for _ in range(steps_per_window):
            with tel.span(obs.SPAN_DATA_WAIT):
                pass
            with tel.span(obs.SPAN_STEP):
                time.sleep(step_s)
            step += 1
        tel.window_event(step, steps=steps_per_window, dirty=dirty)
    return step


def test_cpu_mfu_absent_never_zero(tmp_path, monkeypatch):
    monkeypatch.delenv("TFDL_PEAK_FLOPS", raising=False)
    assert profiler_lib.resolve_peak_flops() is None  # CPU host
    tel = obs.Telemetry(str(tmp_path), run_info={"task": "t"})
    tel.set_step_flops(1e9, n_devices=1)
    _drive_windows(tel)
    tel.close(steps=2)
    window = next(e for e in obs.read_ledger(str(tmp_path))
                  if e["event"] == "step_window")
    # no device peak -> MFU is OMITTED, never a fabricated 0 or a 0/0 crash
    assert "mfu" not in window


def test_mfu_priced_against_env_peak(tmp_path, monkeypatch):
    monkeypatch.setenv("TFDL_PEAK_FLOPS", "1e12")
    tel = obs.Telemetry(str(tmp_path), run_info={"task": "t"})
    tel.set_step_flops(1e9, n_devices=1)
    _drive_windows(tel, step_s=0.005)
    tel.close(steps=2)
    window = next(e for e in obs.read_ledger(str(tmp_path))
                  if e["event"] == "step_window")
    # priced over the window's wall per step (boundary to boundary), not the
    # mean `step` span: dispatch plus backpressure is no measure of a step
    step_s = window["wall_s"] / window["steps"]
    assert window["mfu"] == pytest.approx(1e9 / step_s / 1e12, rel=0.05)
    assert window["wall_s"] >= window["compute_s"]
    assert 0 < window["mfu"] < 1


# -- profiler state machine --------------------------------------------------


def test_disabled_profiler_is_ledger_inert(tmp_path):
    def run(subdir, attach):
        wd = str(tmp_path / subdir)
        tel = obs.Telemetry(wd, run_info={"task": "t"})
        if attach:
            prof = profiler_lib.ContinuousProfiler(tel, every_windows=0)
            tel.set_profiler(prof)
        _drive_windows(tel, n_windows=3)
        tel.close(steps=6)
        return wd, [e["event"] for e in obs.read_ledger(wd)]

    _, plain = run("plain", attach=False)
    wd, with_prof = run("prof", attach=True)
    assert with_prof == plain  # identical event stream — byte-inert
    assert not os.path.isdir(os.path.join(wd, "profile"))  # no capture dirs


def test_profiler_without_workdir_degrades(fake_tracer):
    tel = obs.NULL_TELEMETRY
    prof = profiler_lib.ContinuousProfiler(tel, every_windows=1)
    assert prof.logdir is None and not prof.enabled
    assert prof._begin("cadence") is None
    assert prof.capture_timed(0.01, wait=True) is None
    prof.on_window(step=1, windows=1, alerts=[])  # no crash, no capture
    assert prof.captures == 0 and fake_tracer.dirs == []


def test_capture_during_capture_refused(tmp_path, fake_tracer):
    tel = obs.Telemetry(str(tmp_path), run_info={"task": "t"})
    prof = profiler_lib.ContinuousProfiler(tel, every_windows=1,
                                           capture_steps=2)
    tel.set_profiler(prof)
    rec = prof._begin("cadence")
    assert rec is not None and prof.capturing
    assert prof._begin("cadence") is None  # the running capture wins
    assert prof.capture_timed(0.01) is None  # timed path refuses too
    prof.note_step(0.001)
    prof.note_step(0.001)  # capture_steps reached -> background finalize
    prof.close()  # joins the finalize
    tel.close(steps=2)
    assert prof.captures == 1
    captures = [e for e in obs.read_ledger(str(tmp_path))
                if e["event"] == profiler_lib.PROFILE_CAPTURE_EVENT]
    assert len(captures) == 1
    assert captures[0]["reason"] == "cadence"
    assert captures[0]["steps"] == 2
    # only ONE trace session ever started
    assert len(fake_tracer.dirs) == 1


def test_cadence_capture_ledgers_roofline(tmp_path, fake_tracer, monkeypatch):
    monkeypatch.setenv("TFDL_PEAK_FLOPS", "1e12")
    tel = obs.Telemetry(str(tmp_path), run_info={"task": "t"})
    tel.set_step_flops(1e9, n_devices=1)
    prof = profiler_lib.ContinuousProfiler(tel, every_windows=2,
                                           capture_steps=3)
    tel.set_profiler(prof)
    _drive_windows(tel, n_windows=4, steps_per_window=3)
    tel.close(steps=12)
    events = obs.read_ledger(str(tmp_path))
    rooflines = [e for e in events
                 if e["event"] == profiler_lib.OP_ROOFLINE_EVENT]
    assert rooflines, "cadence capture must ledger an op_roofline"
    r = rooflines[0]
    fracs = r["classes"]
    assert fracs["compute_frac"] == pytest.approx(0.6, abs=0.01)
    assert fracs["collective_frac"] == pytest.approx(0.3, abs=0.01)
    assert fracs["hbm_frac"] == pytest.approx(0.1, abs=0.01)
    assert r["phase"] == "train"
    assert r["mfu"] is not None and r["mfu"] > 0
    assert r["achieved_flops_per_sec_per_chip"] > 0


def test_triggered_postmortem_rate_limited_and_alert_linked(
    tmp_path, fake_tracer
):
    """The injected-regression drill: a step_time health alert auto-captures
    exactly ONE postmortem profile stamped with the alert's id; a second
    trigger inside the rate-limit interval is refused and counted."""
    health = HealthMonitor()
    health.step_time.baseline_windows = 1
    health.step_time.factor = 1.5
    tel = obs.Telemetry(str(tmp_path), run_info={"task": "t"}, health=health)
    prof = profiler_lib.ContinuousProfiler(tel, every_windows=0,
                                           capture_steps=2)
    tel.set_profiler(prof)
    _drive_windows(tel, n_windows=1, step_s=0.002)  # baseline window
    _drive_windows(tel, n_windows=1, step_s=0.02)  # 10x regression -> alert
    _drive_windows(tel, n_windows=1, step_s=0.02)  # finishes the capture
    # a second synthetic alert inside the 300s interval must be refused
    assert prof.trigger({"monitor": "step_time", "alert_id": "x"}) is None
    assert prof.rate_limited == 1
    tel.close(steps=6)
    events = obs.read_ledger(str(tmp_path))
    alerts = [e for e in events if e["event"] == "health_alert"
              and e.get("monitor") == "step_time" and not e.get("resolved")]
    captures = [e for e in events
                if e["event"] == profiler_lib.PROFILE_CAPTURE_EVENT]
    assert len(alerts) == 1 and len(captures) == 1
    assert captures[0]["reason"] == "alert"
    assert captures[0]["alert_id"] == alerts[0]["alert_id"]


def test_capture_timed_runs_off_thread(tmp_path, fake_tracer):
    tel = obs.Telemetry(str(tmp_path), run_info={"task": "t"})
    prof = profiler_lib.ContinuousProfiler(tel)
    tel.set_profiler(prof)
    out = prof.capture_timed(0.05, wait=True)
    assert out is not None and out["status"] == "complete"
    tel.close(steps=0)
    captures = [e for e in obs.read_ledger(str(tmp_path))
                if e["event"] == profiler_lib.PROFILE_CAPTURE_EVENT]
    assert len(captures) == 1
    assert captures[0]["reason"] == "admin"
    assert captures[0]["seconds"] == pytest.approx(0.05)


def test_close_mid_capture_still_ledgers(tmp_path, fake_tracer):
    tel = obs.Telemetry(str(tmp_path), run_info={"task": "t"})
    prof = profiler_lib.ContinuousProfiler(tel, every_windows=1)
    tel.set_profiler(prof)
    assert prof._begin("cadence") is not None
    tel.close(steps=0)  # run ends mid-capture: close() finishes + ledgers
    captures = [e for e in obs.read_ledger(str(tmp_path))
                if e["event"] == profiler_lib.PROFILE_CAPTURE_EVENT]
    assert len(captures) == 1


# -- measured planner costs --------------------------------------------------


def _ledger_roofline(workdir, flops_rate, coll_rate=None):
    tel = obs.Telemetry(workdir, run_info={"task": "t"})
    fields = {"phase": "train",
              "achieved_flops_per_sec_per_chip": flops_rate}
    if coll_rate is not None:
        fields["achieved_collective_bytes_per_sec"] = coll_rate
    tel.event(profiler_lib.OP_ROOFLINE_EVENT, **fields)
    tel.close(steps=0)


def test_measured_costs_from_workdir_last_event_wins(tmp_path):
    from tensorflowdistributedlearning_tpu.parallel import planner

    assert planner.measured_costs_from_workdir(str(tmp_path)) is None
    tel = obs.Telemetry(str(tmp_path), run_info={"task": "t"})
    tel.event(profiler_lib.OP_ROOFLINE_EVENT, phase="train",
              achieved_flops_per_sec_per_chip=2e12)
    tel.event(profiler_lib.OP_ROOFLINE_EVENT, phase="train",
              achieved_flops_per_sec_per_chip=3e12,
              achieved_collective_bytes_per_sec=5e10)
    tel.close(steps=0)
    mc = planner.measured_costs_from_workdir(str(tmp_path))
    assert mc is not None
    assert mc.flops_per_sec_per_chip == pytest.approx(3e12)  # last wins
    assert mc.collective_bytes_per_sec == pytest.approx(5e10)
    assert mc.captures == 2
    assert mc.source == str(tmp_path)


def test_plan_cli_no_rooflines_exits_2(tmp_path, capsys):
    from tensorflowdistributedlearning_tpu.cli import main

    rc = main([
        "plan", "--preset", "cifar10_smoke", "--n-devices", "8",
        "--measured-costs-from", str(tmp_path),
    ])
    captured = capsys.readouterr()
    assert rc == 2
    assert "op_roofline" in captured.err
    assert "--profile-every-windows" in captured.err


def test_plan_cli_measured_provenance(tmp_path, capsys):
    from tensorflowdistributedlearning_tpu.cli import main

    _ledger_roofline(str(tmp_path), flops_rate=2e12, coll_rate=4e10)
    rc = main([
        "plan", "--preset", "cifar10_smoke", "--n-devices", "8",
        "--measured-costs-from", str(tmp_path),
    ])
    out = capsys.readouterr().out
    assert rc == 0
    assert "measured" in out
    assert "analytic" in out  # side-by-side columns


def test_plan_cli_analytic_provenance_hint(capsys):
    from tensorflowdistributedlearning_tpu.cli import main

    rc = main(["plan", "--preset", "cifar10_smoke", "--n-devices", "8"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "analytic" in out
    assert "--measured-costs-from" in out  # how to upgrade the cost model


# -- report / top degraded rendering ----------------------------------------


def test_report_renders_without_captures(tmp_path):
    from tensorflowdistributedlearning_tpu.obs.report import (
        build_report,
        render_report,
    )

    tel = obs.Telemetry(str(tmp_path), run_info={"task": "t"})
    _drive_windows(tel, n_windows=2)
    tel.close(steps=4)
    report = build_report(str(tmp_path))
    text = render_report(report)
    assert report.get("profiling", {}).get("captures", 0) == 0
    assert "mfu" not in report or report["mfu"]["windows"] == 0
    assert text  # renders clean, no crash, no fabricated numbers


def test_top_renders_dash_without_captures(tmp_path):
    from tensorflowdistributedlearning_tpu.obs.top import (
        build_frame,
        render_frame,
    )

    tel = obs.Telemetry(str(tmp_path), run_info={"task": "t"})
    _drive_windows(tel, n_windows=1)
    tel.close(steps=2)
    frame = build_frame(str(tmp_path))
    text = render_frame(frame)
    assert "mfu -" in text or "roofline -" in text


def test_top_renders_roofline_row(tmp_path, fake_tracer, monkeypatch):
    monkeypatch.setenv("TFDL_PEAK_FLOPS", "1e12")
    from tensorflowdistributedlearning_tpu.obs.top import (
        build_frame,
        render_frame,
    )

    tel = obs.Telemetry(str(tmp_path), run_info={"task": "t"})
    tel.set_step_flops(1e9, n_devices=1)
    prof = profiler_lib.ContinuousProfiler(tel, every_windows=1,
                                           capture_steps=2)
    tel.set_profiler(prof)
    _drive_windows(tel, n_windows=2)
    tel.close(steps=4)
    text = render_frame(build_frame(str(tmp_path)))
    assert "roofline" in text and "compute" in text


# -- the headline drill ------------------------------------------------------


@pytest.mark.slow
def test_continuous_profiling_headline_drill(tmp_path, monkeypatch):
    """A real fit run with ``profile_every_windows`` set: a cadence capture
    lands mid-run, its ledgered ``op_roofline`` MFU and the report's goodput
    MFU price the same FLOPs, and the planner re-scores from the workdir with
    measured provenance. The model is a ViT: the 6*params*batch proxy is
    applied to transformers only (a convolutional run ledgers no MFU)."""
    # a peak small enough that a toy model's share of it keeps its digits
    # through the ledger's rounding to four places
    monkeypatch.setenv("TFDL_PEAK_FLOPS", "1e9")
    from tensorflowdistributedlearning_tpu.cli import main
    from tensorflowdistributedlearning_tpu.config import ModelConfig, TrainConfig
    from tensorflowdistributedlearning_tpu.obs.report import build_report
    from tensorflowdistributedlearning_tpu.parallel import planner
    from tensorflowdistributedlearning_tpu.train.fit import ClassifierTrainer

    workdir = str(tmp_path / "run")
    ClassifierTrainer(
        workdir, None,
        ModelConfig(backbone="vit", num_classes=10, input_shape=(32, 32),
                    input_channels=3, patch_size=4, embed_dim=128,
                    vit_layers=4, num_heads=4),
        TrainConfig(checkpoint_every_steps=100, profile_every_windows=2),
    ).fit(batch_size=64, steps=65, eval_every_steps=1000)
    events = obs.read_ledger(workdir)
    rooflines = [e for e in events
                 if e["event"] == profiler_lib.OP_ROOFLINE_EVENT]
    assert rooflines, "the run must ledger at least one op_roofline"
    roofline = rooflines[-1]
    assert roofline["phase"] == "train"
    assert roofline["mfu"] is not None

    report = build_report(workdir)
    goodput_mfu = report["mfu"]["mean"]
    assert goodput_mfu is not None and goodput_mfu > 0
    # the capture prices its 3 steps over their `step` spans, a window over
    # its whole wall per step (loader and fetch waits included). With each
    # clean window put on the capture's basis — its MFU times wall_s over
    # compute_s — the two price the same steady state. Three captured steps
    # on a shared CPU scatter by up to 15% around twenty (12 runs: 0.86 to
    # 1.09 at this size), hence a quarter and not a tenth
    clean = [e for e in events if e["event"] == "step_window"
             and not e["dirty"] and e.get("mfu") is not None]
    assert len(clean) == report["mfu"]["windows"]
    on_span_basis = (
        sum(e["mfu"] * e["wall_s"] / e["compute_s"] * e["steps"] for e in clean)
        / sum(e["steps"] for e in clean)
    )
    assert roofline["mfu"] == pytest.approx(on_span_basis, rel=0.25)
    # the same FLOPs over no less time: the goodput MFU is the lower
    assert goodput_mfu <= on_span_basis

    # planner loop: measured rates from this workdir re-score candidates
    mc = planner.measured_costs_from_workdir(workdir)
    assert mc is not None and mc.flops_per_sec_per_chip > 0
    rc = main([
        "plan", "--preset", "cifar10_smoke", "--n-devices", "8",
        "--measured-costs-from", workdir,
    ])
    assert rc == 0
