"""Host–device overlap for the training loops: dispatch-ahead, deferred fetch.

jax dispatches jitted computations asynchronously, but the trainers used to
defeat that twice per run phase: every log window blocked on a synchronous
``jax.device_get(metrics)`` (draining the device queue before the next step
could be fed), and every eval BATCH pulled its metric deltas to the host.
Keeping the accelerator queue full with asynchronous dispatch and deferred
host fetches is the standard overlap discipline of pjit-era TPU stacks
(arXiv:2204.06514) and generalizes the reference's ``prefetch(2×n_gpus)``
host-overlap idea (arXiv:1605.08695, reference: model.py:319-320) from input
copies to the whole host loop. This module owns the three pieces:

- **bounded dispatch-ahead** (``HostOverlap.track``): the host may run at most
  ``TrainConfig.dispatch_ahead_steps`` dispatched-but-unretired steps past the
  device; beyond the budget it blocks on the oldest in-flight step under the
  ``fetch_wait`` telemetry span, so backpressure is bounded AND measured
  (surfaced per window and in ``telemetry-report``'s goodput split);
- **deferred window metrics** (``HostOverlap.window``/``flush``): a log
  window's scalars start a ``copy_to_host_async`` at the boundary and are
  fetched/emitted at the NEXT boundary, while the device is already running
  window N+1 — TB/ledger events carry the step they describe, arriving one
  window late. Span samples are snapshotted at the boundary so a late-written
  window event still describes its own interval. ``flush()`` runs at every
  eval/checkpoint/preemption/end boundary, so resilience semantics
  (``faults.fire``/``preempt.requested`` ordering, ledger completeness at a
  preemption checkpoint) are unchanged;
- **device-resident eval accumulation** (``merge_metrics_device`` +
  ``fetch_metrics``): the eval accumulator stays a device ``Mean`` pytree,
  merged by a tiny jitted add per batch, with ONE host transfer per eval pass
  (counted in the registry under ``EVAL_FETCH_COUNTER`` — pinned by
  tests/test_async_loop.py) instead of one per batch.

``dispatch_ahead_steps=0`` is the synchronous legacy loop: the window fetch
blocks in place (under the ``step`` span, as before) and nothing is tracked —
the bit-for-bit A/B the bench (``bench.py --async-loop``) and the parity tests
compare against.

All blocked-on-device time here flows through the telemetry span API, so with
tracing enabled (``TrainConfig.trace_sample_rate``) the ``fetch_wait`` waits
appear as sampled spans in ``telemetry-report --export-trace`` timelines
alongside step/eval/checkpoint — the per-unit view of dispatch-ahead
backpressure.
"""

from __future__ import annotations

import dataclasses
import functools
from collections import deque
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from tensorflowdistributedlearning_tpu import obs as obs_lib
from tensorflowdistributedlearning_tpu.ops import metrics as metrics_lib

# registry counter: one increment per eval-pass metric transfer — the
# "exactly one host transfer per eval pass" contract is asserted against it
EVAL_FETCH_COUNTER = "fetch/eval_metrics"


class DispatchBudget:
    """Bounded dispatch-ahead over any loop of device computations.

    ``track(tree)`` once per dispatched step with one of its device outputs;
    past ``budget`` in-flight steps it blocks on the OLDEST one (recorded
    under ``span`` — default the ``fetch_wait`` window span; None records
    nothing) so the host never runs unboundedly ahead of the device.
    ``block_until_ready`` waits for completion without transferring —
    tracking adds no host copies. ``budget <= 0`` disables tracking entirely
    (the caller owns its own sync points).

    A caller that numbers its steps (``track(tree, step)``) gets a completion
    time for each: the moment the wait on a retired step returns — blocked
    or not — is handed to ``Telemetry.step_done``, which says what such a
    time does and does not mean. No synchronisation is added for it."""

    def __init__(
        self,
        telemetry,
        budget: int,
        span: Optional[str] = obs_lib.SPAN_FETCH_WAIT,
    ):
        self._tel = telemetry
        self._budget = int(budget)
        self._span = span
        self._inflight: deque = deque()

    @property
    def budget(self) -> int:
        return self._budget

    def track(self, tree: Any, step: Optional[int] = None) -> None:
        if self._budget <= 0:
            return
        leaf = next(iter(jax.tree.leaves(tree)), None)
        if leaf is None:
            return
        self._inflight.append((step, leaf))
        if len(self._inflight) > self._budget:
            retired, oldest = self._inflight.popleft()
            if self._span is None:
                jax.block_until_ready(oldest)
            else:
                with self._tel.span(self._span):
                    jax.block_until_ready(oldest)
            if retired is not None:
                self._tel.step_done(retired)


def eval_budget(telemetry, dispatch_ahead: int) -> DispatchBudget:
    """The eval pass's in-flight bound: the legacy loop's per-batch
    ``device_get`` throttled eval to ~1 batch in flight as a side effect;
    device-resident accumulation removes that sync, so WITHOUT a bound the
    host would enqueue every eval batch's H2D copy + step at once and a large
    val split could hold its whole input set in HBM. Track the accumulator
    each batch with at least a budget of 1 (even in sync mode — bounded
    memory is not optional), at most the train loop's dispatch-ahead knob.

    ``span=None``: these waits happen INSIDE the eval span, whose wall time
    the eval event already records — a ``fetch_wait`` sample here would sit
    in the histogram until the NEXT train window drained it, double-counting
    eval time as dispatch-ahead backpressure in the goodput split."""
    return DispatchBudget(telemetry, max(1, int(dispatch_ahead)), span=None)


@dataclasses.dataclass
class PendingWindow:
    """One log window's deferred payload: the device metric pytree plus every
    host-side fact the emit needs, captured AT the boundary (wall-clock
    throughput, host-computed lr, the span samples of the window's own
    interval) so nothing is recomputed when the event is written late."""

    step: int
    metrics: Any  # device Metrics pytree (Dict[str, ops.metrics.Mean])
    steps: int
    lr: float
    images_per_sec: Optional[float] = None
    dirty: bool = False
    samples: Optional[Dict[str, list]] = None


class HostOverlap:
    """The trainers' host–device overlap state machine (one per run phase).

    ``emit(record, scalars)`` is the trainer's write-out (TB scalars + ledger
    window event); it fires immediately in sync mode and one boundary late in
    async mode. ``telemetry`` provides the span API the blocked-on-fetch time
    is recorded through (``NULL_TELEMETRY`` works: spans no-op).
    """

    def __init__(
        self,
        telemetry,
        *,
        dispatch_ahead: int = 2,
        emit: Callable[[PendingWindow, Dict[str, float]], None],
    ):
        self._tel = telemetry
        self._emit = emit
        self._tracker = DispatchBudget(telemetry, max(0, int(dispatch_ahead)))
        self._pending: Optional[PendingWindow] = None
        # the loop starts here: its first window begins now, and holds no
        # sample of what ran before it (a previous fold's last steps, the
        # start-up's spans)
        telemetry.drain_window_samples()

    @property
    def async_mode(self) -> bool:
        return self._tracker.budget > 0

    def track(self, metrics: Any, step: Optional[int] = None) -> None:
        """Bounded dispatch-ahead: call once per dispatched train step with its
        metric output and its number. Past the budget, blocks on the OLDEST
        in-flight step (recorded as ``fetch_wait``) so the host never runs
        unboundedly ahead of the device, and reads the clock when that wait
        returns: the retired step's completion time
        (``step_window.step_done_mono``). Sync mode (budget 0) is a no-op —
        the legacy loop's only sync point is the window ``device_get`` — and
        has no completion times."""
        self._tracker.track(metrics, step)

    def window(self, record: PendingWindow) -> None:
        """Log-window boundary. Sync mode fetches and emits in place (the
        ``device_get`` synchronizes on this step, so window span totals are
        real wall time — it counts as step time, exactly the legacy
        accounting). Async mode emits the PREVIOUS window, snapshots this
        window's span samples, starts the host copy, and defers."""
        if not self.async_mode:
            with self._tel.span(obs_lib.SPAN_STEP):
                host = jax.device_get(record.metrics)
            self._emit_window(record, host)
            return
        self.flush()
        record.samples = self._tel.drain_window_samples()
        for leaf in jax.tree.leaves(record.metrics):
            if hasattr(leaf, "copy_to_host_async"):
                leaf.copy_to_host_async()
        self._pending = record

    def flush(self) -> None:
        """Fetch and emit the deferred window, if any. The trainers call this
        at every eval/checkpoint/preemption/end boundary so the ledger is
        complete before any resilience-relevant event is written. Idempotent
        and cheap when nothing is pending."""
        record, self._pending = self._pending, None
        if record is None:
            return
        with self._tel.span(obs_lib.SPAN_FETCH_WAIT):
            host = jax.device_get(record.metrics)
        self._emit_window(record, host)

    def _emit_window(self, record: PendingWindow, host_metrics: Any) -> None:
        # the write-out is host time of the window it falls INTO (the one
        # after the window it describes, in async mode): the boundary
        # snapshot books it there, like every other span. The reduction to
        # scalars is inside: `compute_metrics` divides with jnp, so it waits
        # on the device behind the steps in flight (PERF.md, PR 24)
        with self._tel.span(obs_lib.SPAN_WINDOW_EMIT):
            self._emit(record, self._scalars(record, host_metrics))

    @staticmethod
    def _scalars(record: PendingWindow, host_metrics: Any) -> Dict[str, float]:
        from tensorflowdistributedlearning_tpu.train import step as step_lib

        scalars = step_lib.compute_metrics(host_metrics)
        if record.images_per_sec is not None:
            scalars["throughput/images_per_sec"] = record.images_per_sec
        scalars["lr"] = record.lr
        return scalars


@functools.lru_cache(maxsize=None)
def _merge_jit():
    # Mean.merge is addition of (total, count); a leafwise add over two Mean
    # pytrees IS the K-way streaming merge, and jitting it keeps the eval
    # accumulator device-resident (dispatch only, no host sync per batch)
    return jax.jit(lambda acc, new: jax.tree.map(jnp.add, acc, new))


def merge_metrics_device(acc: Optional[Any], new: Any) -> Any:
    """Device-side streaming metric merge for eval passes: ``None`` starts the
    stream (validating every leaf is a ``Mean`` — the addition-is-merge
    contract ``train.step._merge_stacked_metrics`` enforces for the scan
    paths), subsequent calls add on device."""
    if acc is None:
        for name, leaf in new.items():
            if not isinstance(leaf, metrics_lib.Mean):
                raise TypeError(
                    f"eval metric {name!r} is a {type(leaf).__name__}, not a "
                    "Mean state — the device-resident accumulator merges by "
                    "addition, which is only a valid merge for Mean's "
                    "(total, count); teach merge_metrics_device this type "
                    "before streaming it"
                )
        return new
    return _merge_jit()(acc, new)


def fetch_metrics(acc: Any, telemetry=None) -> Dict[str, float]:
    """THE one host transfer of an eval pass: pull the accumulated device
    metrics and reduce them to floats. Counts the transfer in the telemetry
    registry (``EVAL_FETCH_COUNTER``) so the single-transfer contract is
    testable from ledger-side accounting."""
    if acc is None:
        raise ValueError("fetch_metrics: no eval batches were accumulated")
    if telemetry is not None:
        telemetry.registry.counter(EVAL_FETCH_COUNTER).inc()
    from tensorflowdistributedlearning_tpu.train import step as step_lib

    # an eval pass reports scalars; a vector-valued stream (the decoder's
    # per-expert counts) is a train-window field only
    return step_lib.split_scalars(step_lib.compute_metrics(jax.device_get(acc)))[0]
