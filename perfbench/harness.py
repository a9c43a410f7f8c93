"""The benchmark's own machinery: find a cell's files, hold the chip, observe
the timed path, compare it with the plain reference, reduce ledger and trace
to per-layer metrics, print the result line.

From the program it takes the system under test (``Trainer.train``,
``ClassifierTrainer.fit``), its telemetry ledger and the names the profiler
gives its programs; everything that measures or judges lives here.
"""

from __future__ import annotations

import dataclasses
import gc
import glob
import importlib
import json
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(ROOT, ".perfbench_work")  # listed in .gitignore

# how many of the window's first steps the reference follows
FOLLOWED_STEPS = 3


class NoChipError(SystemExit):
    """No TPU, or fewer chips than the cell asks for: exit code 2, no result."""

    def __init__(self, message: str):
        print(f"perfbench: {message}", file=sys.stderr)
        super().__init__(2)


# ---------------------------------------------------------------------------
# cells are data: BENCHMARK.json names the files
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]  # perfbench/configs/<config>.json
    traffic: Dict[str, Any]  # perfbench/traffic/<traffic>.json
    limits: Dict[str, float]  # perfbench/limits/<cell>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def reference_cfg(self) -> Dict[str, Any]:
        """The sizes as the plain reference reads them."""
        cfg = dict(self.config["model"])
        cfg.update(self.config["train"])
        cfg["multi_grid"] = self.config["multi_grid"]
        return cfg


def _read_json(path: str) -> Dict[str, Any]:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"perfbench: no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    config_file = next(c["file"] for c in bench["configs"] if c["name"] == w["config"])
    home = os.path.join(root, bench["paths"][0])

    def applies(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=_read_json(os.path.join(root, config_file)),
        traffic=_read_json(os.path.join(home, "traffic", f"{w['traffic']}.json")),
        limits=_read_json(os.path.join(home, "limits", f"{workload}.json")),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)],
    )


def require_chips(chips: int):
    """The cell's devices, or exit 2 with no result."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChipError(f"JAX found no backend: {e}")
    if devices[0].platform != "tpu":
        raise NoChipError(f"JAX found no accelerator (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChipError(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def device_report(devices) -> Dict[str, Any]:
    """The device as JAX reports it. On this runtime the allocator's
    ``peak_bytes_in_use`` counts live buffers only; what a running program
    holds besides — its temporaries, such as the activations kept for the
    backward pass — is set aside under ``peak_bytes_reserved`` (a probe with
    7.0 GiB of temporaries read 1.08 GB in use and 7.0 GiB reserved: PERF.md,
    PR 23). The peak on a chip is the two together; both parts are given
    beside the sum."""
    peak = live = reserved = 0
    for d in devices:
        stats = d.memory_stats() or {}
        a = int(stats.get("peak_bytes_in_use", 0))
        b = int(stats.get("peak_bytes_reserved", 0))
        if a + b >= peak:
            peak, live, reserved = a + b, a, b
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
        "peak_bytes_in_use": live,
        "peak_bytes_reserved": reserved,
    }


# ---------------------------------------------------------------------------
# observing the timed path
# ---------------------------------------------------------------------------


def _to_host(tree):
    import jax

    return jax.device_get(tree)


def optimizer_moments(opt_state) -> Dict[str, Any]:
    """The first-moment trees inside an optax state: Adam's (mu, nu) or the
    momentum trace, whichever node of the chain holds one."""
    stack = [opt_state]
    while stack:
        node = stack.pop()
        fields = getattr(node, "_fields", ())  # optax states are named tuples
        if "mu" in fields and "nu" in fields:
            return {"mu": node.mu, "nu": node.nu}
        if "trace" in fields:
            return {"trace": node.trace}
        if isinstance(node, (tuple, list)) and not fields:
            stack.extend(node)
    raise ValueError("no first-moment state found in the optimizer state")


class Probe:
    """Wraps the two compiled programs the loop calls each step — the input
    program and the train step — without changing what they do. It copies
    the first steps' feeds and results to the host, opens the window at a
    step boundary with the device drained, closes it at the first boundary at
    or after ``seconds`` with the device drained again, and then asks the
    program to stop through its own preemption request."""

    def __init__(self, *, warmup_steps: int, seconds: float, trace_dir: Optional[str],
                 trace_seconds: float, on_close: Callable[[], None]):
        self.warmup_steps = max(int(warmup_steps), FOLLOWED_STEPS)
        self.seconds = float(seconds)
        self.trace_dir = trace_dir
        self.trace_seconds = float(trace_seconds)
        self.on_close = on_close
        self.calls = 0
        self.raw: List[Tuple[int, Any]] = []  # (step index, rows fed to the input program)
        self.fed: List[Any] = []  # batches fed to the train step
        self.losses: List[float] = []
        self.moments_after_one = None
        self.stats_before = self.stats_after_one = None  # BatchNorm running statistics
        self.params_after_followed = None
        self.t_open = self.t_close = None
        self.steps_in_window = 0
        self.closed = False
        self.trace_on = False
        self.trace_span: Optional[Tuple[float, float]] = None
        self.trace_steps = 0

    # -- the input program ---------------------------------------------------

    def wrap_prepare(self, inner):
        def prepare(step, batch):
            if len(self.raw) < FOLLOWED_STEPS:
                self.raw.append((int(step), _to_host(batch)))
            return inner(step, batch)

        return prepare

    # -- the train step --------------------------------------------------------

    def wrap_step(self, real):
        import jax

        def step(state, batch):
            k = self.calls
            now = time.perf_counter
            if k == self.warmup_steps:
                jax.block_until_ready(state)
                if self.trace_dir is not None:
                    options = jax.profiler.ProfileOptions()
                    options.python_tracer_level = 0
                    jax.profiler.start_trace(self.trace_dir, profiler_options=options)
                    self.trace_on = True
                    self._trace_t0, self._trace_k0 = now(), k
                self.t_open = now()
            elif self.t_open is not None and not self.closed:
                elapsed = now() - self.t_open
                if self.trace_on and elapsed >= self.trace_seconds:
                    jax.block_until_ready(state)
                    self.trace_span = (self._trace_t0, now())
                    self.trace_steps = k - self._trace_k0
                    jax.profiler.stop_trace()
                    self.trace_on = False
                if elapsed >= self.seconds:
                    jax.block_until_ready(state)
                    self.t_close = now()
                    self.steps_in_window = k - self.warmup_steps
                    self.closed = True
                    if self.trace_on:
                        self.trace_span = (self._trace_t0, self.t_close)
                        self.trace_steps = k - self._trace_k0
                        jax.profiler.stop_trace()
                        self.trace_on = False
                    self.on_close()
            followed = k < FOLLOWED_STEPS
            if followed:
                self.fed.append(_to_host(batch))
            if k == 0:
                self.stats_before = _to_host(state.batch_stats)  # the step donates its state
            new_state, metrics = real(state, batch)
            if followed:
                loss = _to_host(metrics["loss"])
                self.losses.append(float(loss.total) / float(loss.count))
                if k == 0:
                    self.moments_after_one = _to_host(optimizer_moments(new_state.opt_state))
                    self.stats_after_one = _to_host(new_state.batch_stats)
                if k == FOLLOWED_STEPS - 1:
                    self.params_after_followed = _to_host(new_state.params)
            self.calls += 1
            return new_state, metrics

        return step

    def observed(self, norm_decay: float) -> Dict[str, Any]:
        """What the followed steps produced. The first step's batch statistics
        are worked out from the running ones it moved: new = decay * old +
        (1 - decay) * batch."""
        import numpy as np

        from perfbench.weights import flatten

        before, after = flatten(self.stats_before), flatten(self.stats_after_one)
        return {
            "stats1": {
                k: (np.asarray(after[k], np.float64) - norm_decay * np.asarray(before[k], np.float64))
                / (1.0 - norm_decay)
                for k in after
            },
            "losses": self.losses,
            "moments": {k: flatten(v) for k, v in self.moments_after_one.items()},
            "params": flatten(self.params_after_followed),
        }


class Phases:
    """Wall seconds of a run's phases after the window, with the compiles and
    compile-cache hits that fell into each: what a run costs beyond
    ``setup_s`` and the window, printed on stderr."""

    def __init__(self):
        from jax import monitoring

        self.rows: List[Tuple[str, float, int, int]] = []
        self._t = time.perf_counter()
        self._hits = self._misses = 0

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self._hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self._misses += 1

        monitoring.register_event_listener(on_event)

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.rows.append((name, now - self._t, self._hits, self._misses))
        self._t, self._hits, self._misses = now, 0, 0

    def report(self) -> Dict[str, float]:
        for name, seconds, hits, misses in self.rows:
            print(f"perfbench: phase {name} {seconds:.1f} s "
                  f"(cache hits {hits}, misses {misses})", file=sys.stderr)
        return {name: seconds for name, seconds, _, _ in self.rows}


def free_device_memory() -> None:
    """Drop what the program left on the device before the reference runs."""
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


# ---------------------------------------------------------------------------
# ledger and trace
# ---------------------------------------------------------------------------


def read_ledger(workdir: str) -> List[Dict[str, Any]]:
    events = []
    for path in sorted(glob.glob(os.path.join(workdir, "telemetry*.jsonl"))):
        with open(path, encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if line:
                    events.append(json.loads(line))
    return events


@dataclasses.dataclass
class Run:
    """What a metric reader may look at."""

    cell: Cell
    ledger: List[Dict[str, Any]]  # every event of the run
    windows: List[Dict[str, Any]]  # the step_window events wholly inside the window
    global_batch: int
    steps: int  # train steps completed in the measured window
    wall_s: float  # the measured window
    setup_s: float
    trace: Optional[Any]  # perfbench.xtrace.Trace of the traced part, or None
    trace_steps: int
    trace_wall_s: float
    device: Dict[str, Any]
    peaks: Any
    _step_buckets: Optional[Tuple[Dict[str, float], int]] = None

    def step_buckets(self) -> Tuple[Dict[str, float], int]:
        """(device seconds per op bucket inside the step program, its whole
        executions) on device 0 of the traced part; two readers share it."""
        if self._step_buckets is None:
            from perfbench import xtrace

            needle = self.cell.traffic["programs"]["step"]
            _, calls = xtrace.module_time_s(self.trace, needle)
            self._step_buckets = (
                xtrace.bucket_time_s(
                    xtrace.ops_inside(self.trace, needle), self.global_batch // self.cell.chips
                ),
                calls,
            )
        return self._step_buckets

    def window_sum(self, key: str) -> float:
        return float(sum(w.get(key, 0.0) for w in self.windows))

    def window_steps(self) -> int:
        return int(sum(w["steps"] for w in self.windows))

    def window_wall_s(self) -> float:
        """Wall time the whole windows took, from the program's own clean
        per-window rate (a dirty window carries none and is left out)."""
        return float(
            sum(
                w["steps"] * self.global_batch / w["images_per_sec"]
                for w in self.windows
                if w.get("images_per_sec")
            )
        )


def windows_inside(ledger, first_step: int, last_step: int):
    """step_window events whose steps all lie in (first_step, last_step]."""
    return [
        e
        for e in ledger
        if e.get("event") == "step_window"
        and e["step"] - e["steps"] >= first_step
        and e["step"] <= last_step
    ]


def read_metrics(run: Run, wanted: List[Dict[str, Any]]) -> Dict[str, Dict[str, Any]]:
    """One reader per per-layer metric, found by its name: the file
    ``metrics/<name with . as _>.py`` holds ``read(run)``. A reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for metric in wanted:
        module = importlib.import_module("perfbench.metrics." + metric["name"].replace(".", "_"))
        value = module.read(run)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


# ---------------------------------------------------------------------------
# the result line
# ---------------------------------------------------------------------------


def emit(result: Dict[str, Any], checks: Dict[str, Tuple[float, float]]) -> None:
    """Checks as the last lines of stderr and as the last key of the one JSON
    line that ends stdout."""
    result = dict(result)
    result["checks"] = {k: [float(v), float(lim)] for k, (v, lim) in checks.items()}
    sys.stdout.flush()
    for name, value in result.get("read_not_compared", {}).items():
        print(f"read {name} = {value:.6g} (not compared)", file=sys.stderr)
    for name, (value, limit) in checks.items():
        verdict = "ok" if value <= limit else "OVER"
        print(f"check {name} = {value:.6g} (limit {limit:.6g}) {verdict}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
