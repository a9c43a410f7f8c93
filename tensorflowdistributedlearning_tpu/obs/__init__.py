"""Unified telemetry: metrics registry, JSONL run ledger, trainer spans,
recompile detection, and the goodput report.

The reference harness had no profiler story at all (SURVEY §5.1) and this
repo's observability used to be three disconnected islands (``utils/profiling``
step timing, ``utils/xplane`` op breakdowns, ``utils/summary`` TB scalars) with
no durable machine-readable record of what a run did. This package is the
layer that ties them together, the way production TPU training is actually
operated (pjit/TPUv4-scale jobs run off step-time/throughput telemetry and
recompile tracking — Yoo et al., arXiv:2204.06514; TensorFlow shipped
metrics+tracing as a core subsystem, Abadi et al., arXiv:1605.08695):

- ``obs.metrics``   — counters, gauges, time-histograms (p50/p90/p99); the ONE
  step-timing implementation (``utils.profiling.StepTimer`` delegates here);
- ``obs.ledger``    — append-only ``telemetry.jsonl`` run ledger in the workdir
  (degrades to a warning when the workdir is unwritable — never crashes
  training);
- ``obs.recompile`` — ``jax.monitoring``-based compile listener that counts and
  timestamps post-warmup recompilations, the silent goodput killer on TPU;
- ``obs.telemetry`` — the ``Telemetry`` façade + span API the trainers wire in
  (data-wait vs step-compute split per log window, eval/checkpoint/memory
  events);
- ``obs.report``    — merges the ledger with ``utils.xplane.op_breakdown`` into
  one goodput report (CLI: ``telemetry-report <workdir>``);
- ``obs.trace``     — request/step-granular trace/span layer (trace_id/span_id/
  parent, host clock only) persisted as sampled ``trace`` ledger events and
  exportable as Chrome/Perfetto trace-event JSON
  (``telemetry-report --export-trace``);
- ``obs.health``    — online health monitors (NaN/Inf loss guard, loss-spike
  MAD detector, step-time regression, serving SLO error budget) emitting
  structured ``health_alert`` ledger events;
- ``obs.profiler``  — continuous profiling: bounded windowed ``jax.profiler``
  captures on a cadence, on demand, and at alert chokepoints; per-op roofline
  classification and achieved-vs-peak MFU ledgered as ``profile_capture`` /
  ``op_roofline`` events that feed the planner's measured cost model;
- ``obs.scopes``    — the registry of the ``jax.named_scope``s the program
  opens, and the ``program_scopes`` record every compiled train-step program
  writes once: which scope and pass each of its HLO ops came from, so a
  capture's device time reads by layer (``op_roofline.by_scope``) and not by
  ``%fusion.N``.
"""

from tensorflowdistributedlearning_tpu.obs.capacity import (
    COST_EVENT,
    WATERMARK_EVENT,
    CostMeter,
    WatermarkTracker,
)
from tensorflowdistributedlearning_tpu.obs.compare import (
    compare_workdirs,
    load_registry,
    register_run,
    run_summary,
)
from tensorflowdistributedlearning_tpu.obs.fleet import (
    STRAGGLER_ALERT_EVENT,
    discover_ledgers,
    fleet_section,
    fleet_summary,
)
from tensorflowdistributedlearning_tpu.obs.health import (
    HEALTH_ALERT_EVENT,
    HeadroomMonitor,
    HealthAbortError,
    HealthMonitor,
    SloTracker,
)
from tensorflowdistributedlearning_tpu.obs.ledger import (
    LEDGER_FILENAME,
    RunLedger,
    flush_all_ledgers,
    per_process_filename,
    read_ledger,
    read_ledger_with_errors,
)
from tensorflowdistributedlearning_tpu.obs.metrics import (
    Counter,
    Gauge,
    MetricsRegistry,
    TimeHistogram,
    time_summary,
)
from tensorflowdistributedlearning_tpu.obs.profiler import (
    OP_ROOFLINE_EVENT,
    PROFILE_CAPTURE_EVENT,
    ContinuousProfiler,
    build_roofline,
    resolve_peak_flops,
)
from tensorflowdistributedlearning_tpu.obs.recompile import RecompileDetector
from tensorflowdistributedlearning_tpu.obs.telemetry import (
    NULL_TELEMETRY,
    PREFETCH_DEPTH_HISTOGRAM,
    SPAN_BARRIER,
    SPAN_CHECKPOINT,
    SPAN_DATA_WAIT,
    SPAN_DISPATCH_PREPARE,
    SPAN_DISPATCH_STEP,
    SPAN_EVAL,
    SPAN_FETCH_WAIT,
    SPAN_IMAGE_SUMMARY,
    SPAN_STEP,
    SPAN_WINDOW_EMIT,
    Telemetry,
)
from tensorflowdistributedlearning_tpu.obs.trace import (
    NULL_TRACER,
    TRACE_EVENT,
    TraceContext,
    Tracer,
    export_chrome_trace,
    write_chrome_trace,
)

__all__ = [
    "COST_EVENT",
    "HEALTH_ALERT_EVENT",
    "PREFETCH_DEPTH_HISTOGRAM",
    "SPAN_BARRIER",
    "SPAN_CHECKPOINT",
    "SPAN_DATA_WAIT",
    "SPAN_DISPATCH_PREPARE",
    "SPAN_DISPATCH_STEP",
    "SPAN_EVAL",
    "SPAN_FETCH_WAIT",
    "SPAN_IMAGE_SUMMARY",
    "SPAN_STEP",
    "SPAN_WINDOW_EMIT",
    "STRAGGLER_ALERT_EVENT",
    "TRACE_EVENT",
    "WATERMARK_EVENT",
    "CostMeter",
    "Counter",
    "Gauge",
    "HeadroomMonitor",
    "HealthAbortError",
    "HealthMonitor",
    "LEDGER_FILENAME",
    "MetricsRegistry",
    "NULL_TELEMETRY",
    "NULL_TRACER",
    "OP_ROOFLINE_EVENT",
    "PROFILE_CAPTURE_EVENT",
    "ContinuousProfiler",
    "RecompileDetector",
    "RunLedger",
    "SloTracker",
    "Telemetry",
    "TimeHistogram",
    "TraceContext",
    "Tracer",
    "WatermarkTracker",
    "build_roofline",
    "compare_workdirs",
    "discover_ledgers",
    "export_chrome_trace",
    "fleet_section",
    "fleet_summary",
    "flush_all_ledgers",
    "load_registry",
    "per_process_filename",
    "read_ledger",
    "read_ledger_with_errors",
    "register_run",
    "resolve_peak_flops",
    "run_summary",
    "time_summary",
    "write_chrome_trace",
]
