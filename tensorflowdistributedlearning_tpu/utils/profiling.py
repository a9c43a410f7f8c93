"""Tracing / profiling utilities.

The reference had NO profiler integration — its only performance artifacts were a
param-count probe and docstring notes ("NCHW ~10% faster", reference: model.py:45-46,
444-445; SURVEY §5.1). This module supplies the subsystem the reference lacked:

- ``trace``: context manager around ``jax.profiler`` writing TensorBoard-viewable
  traces (XLA op timeline, HBM usage) to a log dir;
- ``StepTimer``: wall-clock per-step timing that ends in ``block_until_ready`` —
  dispatch is asynchronous, so a clock stopped without it times the enqueue;
- ``annotate``: named trace spans (``jax.profiler.TraceAnnotation``) so host-side
  phases (decode, shard, step) are visible in the timeline.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, Iterator, List, Optional

import jax


@contextlib.contextmanager
def trace(logdir: str) -> Iterator[None]:
    """Capture a ``jax.profiler`` trace for the enclosed block; view with
    TensorBoard's profile plugin pointed at ``logdir``."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named span visible in profiler timelines (host + device)."""
    return jax.profiler.TraceAnnotation(name)


def sync(tree: Any) -> None:
    """Wait until every array in ``tree`` has been computed."""
    jax.block_until_ready(
        [x for x in jax.tree.leaves(tree) if isinstance(x, jax.Array)]
    )


class StepTimer:
    """Accumulates per-step wall times; ``summary()`` reports
    mean/p50/p90/p99 and optional items/sec. Synchronization is the caller's
    choice: pass the step output to ``stop`` and it is ``sync``'d before the
    clock stops.

    The samples live in an ``obs.metrics.TimeHistogram`` and the percentile
    math is ``obs.metrics.time_summary`` — the ONE step-timing implementation
    the telemetry spans, the benchmarks (bench.py), and this timer share."""

    def __init__(self, items_per_step: Optional[int] = None):
        from tensorflowdistributedlearning_tpu.obs.metrics import TimeHistogram

        self.items_per_step = items_per_step
        self._hist = TimeHistogram("step")
        self._t0: Optional[float] = None

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(self, outputs: Any = None) -> float:
        if self._t0 is None:
            raise RuntimeError("StepTimer.stop() without start()")
        if outputs is not None:
            sync(outputs)
        dt = time.perf_counter() - self._t0
        self._hist.record(dt)
        self._t0 = None
        return dt

    @contextlib.contextmanager
    def step(self):
        """``with timer.step(): out = train_step(...); sync(out)`` — the CALLER must
        sync inside the block (or use start()/stop(outputs) which syncs for you);
        otherwise only async dispatch is measured."""
        self.start()
        yield
        self.stop()

    @property
    def times(self) -> List[float]:
        return self._hist.samples

    def summary(self, skip_first: int = 1) -> Dict[str, float]:
        """Timing stats, excluding the first ``skip_first`` (compile) steps."""
        if not len(self._hist):
            raise RuntimeError("StepTimer.summary(): no steps recorded")
        out = self._hist.summary(skip_first=skip_first)
        out["steps"] = out.pop("count")
        if self.items_per_step:
            out["items_per_sec"] = self.items_per_step / out["mean_s"]
        return out


def memory_stats() -> Dict[str, Dict[str, int]]:
    """Per-device live memory statistics (bytes) — the HBM observability knob
    for sizing batch/remat/parallelism choices. Keys are device strings; values
    are whatever the backend reports (TPU: ``bytes_in_use``, ``peak_bytes_in_use``,
    ``bytes_limit``, ...). Devices whose runtime does not implement the query
    (e.g. some CPU builds) are simply absent."""
    import jax

    out: Dict[str, Dict[str, int]] = {}
    for device in jax.local_devices():
        stats = getattr(device, "memory_stats", None)
        if stats is None:
            continue
        try:
            value = stats()
        except Exception:  # noqa: BLE001 — unsupported backend
            continue
        if value:
            out[str(device)] = dict(value)
    return out


def log_memory(logger_fn=None) -> Dict[str, Dict[str, int]]:
    """Log (and return) a compact per-device HBM summary: in-use / peak / limit."""
    import logging as _logging

    log = logger_fn or _logging.getLogger(__name__).info
    stats = memory_stats()
    for dev, s in stats.items():
        in_use = s.get("bytes_in_use", 0)
        peak = s.get("peak_bytes_in_use", 0)
        limit = s.get("bytes_limit", 0)
        log(
            "%s: %.1f MiB in use (peak %.1f MiB, limit %.1f MiB)",
            dev, in_use / 2**20, peak / 2**20, limit / 2**20,
        )
    return stats
