"""Regression sentinel: replay committed BENCH_*.json baselines, fail on drift.

The repo commits its performance history (BENCH_ASYNC.json, BENCH_SERVE.json)
but until now nothing ENFORCED it — a PR could halve serving throughput or
stall the async host loop and every test would stay green. This tool is the
CI gate: run the same benches fresh, compare the numbers that matter against
the committed baselines with noise-aware slack, exit nonzero on regression.

What is compared, and with how much slack, is deliberately asymmetric:

- **Dimensionless ratios transfer across machines** and get tight bounds:
  ``step_time_ratio_async_over_sync`` (async must stay not-slower than sync),
  ``speedup_batched_vs_per_request`` (coalescing must keep paying for
  itself), ``final_params_bit_identical`` and ``post_warmup_recompiles`` are
  HARD (no slack: bitwise parity and zero recompiles are correctness, not
  performance).
- **Absolute wall-clock numbers do not transfer** (a shared CI runner is not
  the box that produced the baseline) and get loose multiplicative slack
  (default 1.75x): they only catch the catastrophic class — a 2x step-time
  or half-throughput regression — which is exactly the class that must never
  land silently.

Usage (CI runs the first form ahead of tier-1)::

    python tools/regression_sentinel.py --check
    python tools/regression_sentinel.py --check --fresh-async A.json \
        --fresh-serve S.json          # compare pre-computed results only

``--fresh-*`` skips running the benches (tests inject doctored results
through it; operators can re-check an old run). Without them the sentinel
runs ``bench.py --async-loop`` and ``tools/bench_serve.py`` on the CPU shape.

The ``records`` bench likewise REPLAYS the committed RECORDS_BENCH.json
(tools/bench_records.py): resume bit-parity and the serviced trainer's
data_wait ceiling are hard, multi-worker scaling and the native-vs-PIL
end-to-end decode ratio are dimensionless floors, and a ``--fresh-records``
record additionally gates per-worker records/sec against machine-drift
slack.

The ``kernels`` bench REPLAYS the committed BENCH_SERVE.json ``kernels`` +
``quant`` sections (bench_serve --quant records both): per-kernel speedup vs
the XLA twin (floor 1.0 on TPU where the Pallas int8/fused kernels must win;
a 0.5 dispatch tripwire off-TPU where both sides run the same dequantize-f32
fallback), int8-compute rps/chip >= int8-store at no-worse p99, and — hard —
zero post-warmup recompiles plus a passing quantize-check for the
int8-compute artifact.

The ``fleet`` bench REPLAYS the committed BENCH_SERVE.json ``fleet`` section
(bench_serve --fleet is too heavy for every CI run): the committed 2-replica
scaling must clear the 1.6x floor, every replica must report zero post-warmup
recompiles, the saturation probe must have shed with Retry-After and zero
non-drain 5xx, and the kill soak must have converged with zero lost accepted
requests — all dimensionless/hard, so no machine slack applies. A
``--fresh-serve`` record carrying its own ``fleet`` section is gated instead.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
from typing import Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# absolute wall-clock numbers (ms, rps): machine drift is real, only the
# catastrophic class must fail — 1.75x keeps an injected 2x regression
# failing while CI-runner noise passes
DEFAULT_WALL_SLACK = 1.75
# async/sync step-time ratio: dimensionless, transfers across machines; the
# local spec is <= 1.05 (bench.py --check), CI allows shared-runner noise
DEFAULT_ASYNC_RATIO_LIMIT = 1.3
# batched/per-request speedup may shrink to this fraction of the committed
# value before it counts as a regression (dimensionless but scheduling-noisy
# on 2-core runners)
DEFAULT_SPEEDUP_FLOOR_FRAC = 0.5
# p99 tail latency is the noisiest number in the set: on an oversubscribed
# CI runner the tail legitimately swings several-fold while throughput holds
# (measured 5x on the 1-core driver box with every other gate green), so
# only the order-of-magnitude class fails — a genuinely serialized request
# path also collapses requests_per_sec and the speedup, which are tighter
DEFAULT_P99_SLACK = 6.0
# peak HBM is near-deterministic for a fixed config (the allocator's
# lifetime peak, not a timing), so the band is far tighter than wall-clock:
# 1.25x catches a working-set regression (an extra params copy, an
# un-donated buffer) while tolerating allocator/version jitter. Only the
# GROWTH direction gates — a smaller peak is an improvement.
DEFAULT_HBM_SLACK = 1.25


def _finding(
    bench: str,
    metric: str,
    baseline,
    fresh,
    limit: str,
    ok: bool,
) -> Dict:
    return {
        "bench": bench,
        "metric": metric,
        "baseline": baseline,
        "fresh": fresh,
        "limit": limit,
        "ok": bool(ok),
    }


def check_async(
    baseline: Dict,
    fresh: Dict,
    *,
    wall_slack: float = DEFAULT_WALL_SLACK,
    ratio_limit: float = DEFAULT_ASYNC_RATIO_LIMIT,
    hbm_slack: float = DEFAULT_HBM_SLACK,
) -> List[Dict]:
    """BENCH_ASYNC.json comparisons (bench.py --async-loop output shape)."""
    out: List[Dict] = []
    base_ms = (baseline.get("async") or {}).get("step_time_ms")
    fresh_ms = (fresh.get("async") or {}).get("step_time_ms")
    if base_ms and fresh_ms:
        out.append(_finding(
            "async", "async.step_time_ms", base_ms, fresh_ms,
            f"<= {wall_slack}x baseline", fresh_ms <= wall_slack * base_ms,
        ))
    base_hbm = baseline.get("peak_hbm_bytes")
    fresh_hbm = fresh.get("peak_hbm_bytes")
    if base_hbm and fresh_hbm:
        # memory is capacity, not speed: a run that silently grows its
        # working set OOMs the flagship shape long before CI notices a
        # timing change (only gated where the backend reports the peak)
        out.append(_finding(
            "async", "peak_hbm_bytes", base_hbm, fresh_hbm,
            f"<= {hbm_slack}x baseline", fresh_hbm <= hbm_slack * base_hbm,
        ))
    ratio = fresh.get("step_time_ratio_async_over_sync")
    if ratio is not None:
        out.append(_finding(
            "async", "step_time_ratio_async_over_sync",
            baseline.get("step_time_ratio_async_over_sync"), ratio,
            f"<= {ratio_limit}", ratio <= ratio_limit,
        ))
    parity = fresh.get("final_params_bit_identical")
    if parity is not None:
        out.append(_finding(
            "async", "final_params_bit_identical", True, parity,
            "== true (hard)", bool(parity),
        ))
    return out


def check_serve(
    baseline: Dict,
    fresh: Dict,
    *,
    wall_slack: float = DEFAULT_WALL_SLACK,
    speedup_floor_frac: float = DEFAULT_SPEEDUP_FLOOR_FRAC,
    p99_slack: float = DEFAULT_P99_SLACK,
) -> List[Dict]:
    """BENCH_SERVE.json comparisons (tools/bench_serve.py output shape)."""
    out: List[Dict] = []
    base_b = baseline.get("batched") or {}
    fresh_b = fresh.get("batched") or {}
    if base_b.get("requests_per_sec") and fresh_b.get("requests_per_sec"):
        floor = base_b["requests_per_sec"] / wall_slack
        out.append(_finding(
            "serve", "batched.requests_per_sec",
            base_b["requests_per_sec"], fresh_b["requests_per_sec"],
            f">= baseline / {wall_slack}",
            fresh_b["requests_per_sec"] >= floor,
        ))
    base_p99 = (base_b.get("latency_ms") or {}).get("p99")
    fresh_p99 = (fresh_b.get("latency_ms") or {}).get("p99")
    if base_p99 and fresh_p99:
        out.append(_finding(
            "serve", "batched.latency_ms.p99", base_p99, fresh_p99,
            f"<= {p99_slack}x baseline", fresh_p99 <= p99_slack * base_p99,
        ))
    # serving efficiency (the cost-per-qps lens): per-chip request rate —
    # on a fixed-shape runner this tracks requests_per_sec, but the
    # committed number stays comparable when the device count changes
    base_rpc = base_b.get("rps_per_chip")
    fresh_rpc = fresh_b.get("rps_per_chip")
    if base_rpc and fresh_rpc:
        out.append(_finding(
            "serve", "batched.rps_per_chip", base_rpc, fresh_rpc,
            f">= baseline / {wall_slack}",
            fresh_rpc >= base_rpc / wall_slack,
        ))
    base_speedup = baseline.get("speedup_batched_vs_per_request")
    fresh_speedup = fresh.get("speedup_batched_vs_per_request")
    if base_speedup and fresh_speedup:
        floor = max(1.0, speedup_floor_frac * base_speedup)
        out.append(_finding(
            "serve", "speedup_batched_vs_per_request",
            base_speedup, fresh_speedup,
            f">= max(1.0, {speedup_floor_frac} x baseline)",
            fresh_speedup >= floor,
        ))
    recompiles = fresh.get("post_warmup_recompiles")
    if recompiles is not None:
        out.append(_finding(
            "serve", "post_warmup_recompiles", 0, recompiles,
            "== 0 (hard)", recompiles == 0,
        ))
    return out


# the quant-kernel acceptance bars (BENCH_SERVE.json ``kernels`` + ``quant``
# sections): on TPU the Pallas int8/fused kernels must BEAT their XLA twins
# (that win is why they exist); off-TPU both comparison sides run the same
# dequantize-f32 fallback, so the ratio is a dispatch-overhead tripwire —
# 0.5 fails only the catastrophic class (a wrapper that doubled the cost)
# while tolerating tiny-shape scheduling noise on shared runners. The
# int8-compute-vs-int8-store serving ratio is the ISSUE-20 acceptance bar:
# >= 1.0 on TPU (the MXU win), >= 0.9 off-TPU (the fallback must stay near
# parity with dequantize-in-graph or the spec costs CPU users real rps).
DEFAULT_KERNEL_TPU_SPEEDUP_FLOOR = 1.0
DEFAULT_KERNEL_CPU_SPEEDUP_FLOOR = 0.5
DEFAULT_INT8_COMPUTE_TPU_RATIO_FLOOR = 1.0
DEFAULT_INT8_COMPUTE_CPU_RATIO_FLOOR = 0.9


def check_kernels(
    baseline: Dict,
    fresh: Optional[Dict] = None,
    *,
    tpu_speedup_floor: float = DEFAULT_KERNEL_TPU_SPEEDUP_FLOOR,
    cpu_speedup_floor: float = DEFAULT_KERNEL_CPU_SPEEDUP_FLOOR,
) -> List[Dict]:
    """Replay the BENCH_SERVE.json quant-kernel gates (bench_serve --quant
    records both sections; too heavy to re-run every CI pass):

    - per-kernel speedup vs the XLA twin (``kernels`` section): floor 1.0
      on TPU, the 0.5 dispatch tripwire elsewhere — dimensionless, no
      machine slack;
    - int8-compute rps/chip >= int8-store x platform floor at no-worse p99
      (``quant.precisions``): switching the arithmetic must never cost
      throughput against the storage-only artifact it replaces;
    - zero post-warmup recompiles serving the int8-compute artifact and a
      passing quantize-check verdict — both HARD (correctness).

    A ``--fresh-serve`` record carrying its own sections is gated instead.
    """
    record = baseline
    if fresh and (fresh.get("kernels") or fresh.get("quant")):
        record = fresh
    kernels = record.get("kernels")
    quant = record.get("quant") or {}
    out: List[Dict] = []
    if not kernels and not quant:
        raise ValueError(
            "no kernels/quant sections in the serve record — run "
            "tools/bench_serve.py --quant and commit the refreshed baseline"
        )
    if kernels:
        on_tpu = kernels.get("platform") == "tpu"
        floor = tpu_speedup_floor if on_tpu else cpu_speedup_floor
        label = "tpu kernel floor" if on_tpu else "cpu dispatch tripwire"
        for name in ("matmul", "conv"):
            entry = kernels.get(name) or {}
            speedup = entry.get("speedup")
            if speedup is None:
                continue
            out.append(_finding(
                "kernels", f"{name}.speedup_vs_xla", floor, speedup,
                f">= {floor} ({label})", speedup >= floor,
            ))
    precisions = quant.get("precisions") or {}
    comp = precisions.get("int8-compute") or {}
    store = precisions.get("int8") or {}
    comp_rpc = comp.get("rps_per_chip") or comp.get("requests_per_sec")
    store_rpc = store.get("rps_per_chip") or store.get("requests_per_sec")
    if comp_rpc and store_rpc:
        on_tpu = quant.get("backend") == "tpu"
        floor = (
            DEFAULT_INT8_COMPUTE_TPU_RATIO_FLOOR
            if on_tpu
            else DEFAULT_INT8_COMPUTE_CPU_RATIO_FLOOR
        )
        ratio = round(comp_rpc / store_rpc, 3)
        out.append(_finding(
            "kernels", "int8_compute.rps_per_chip_vs_int8_store",
            floor, ratio, f">= {floor}", ratio >= floor,
        ))
        p99_ratio = comp.get("p99_ratio_vs_int8_store")
        if p99_ratio is not None:
            out.append(_finding(
                "kernels", "int8_compute.p99_ratio_vs_int8_store",
                1.25, p99_ratio, "<= 1.25", p99_ratio <= 1.25,
            ))
    if comp:
        recompiles = comp.get("post_warmup_recompiles")
        out.append(_finding(
            "kernels", "int8_compute.post_warmup_recompiles",
            0, recompiles, "== 0 (hard)", recompiles == 0,
        ))
        verdict = (quant.get("quant_check") or {}).get("int8-compute")
        if verdict is not None:
            out.append(_finding(
                "kernels", "int8_compute.quant_check_passed",
                True, verdict.get("passed"), "== True (hard)",
                bool(verdict.get("passed")),
            ))
    return out


# the fleet acceptance floor: 2 replicas must buy >= 1.6x single-replica
# throughput (scaling efficiency 0.8) — below that the tier's premise
# (capacity scales with replicas) is broken, whatever the machine
DEFAULT_FLEET_SCALING_FLOOR = 1.6

# data-service floors (RECORDS_BENCH.json multi_worker section): the best
# worker count must beat one worker by this much (dimensionless — if adding
# workers stops paying, the service's premise broke), and the serviced
# trainer's mean per-window data_wait fraction must stay ~0 (the ISSUE-12
# acceptance ceiling). Both replay the COMMITTED record by default, like the
# fleet gates — a PR touching the input path must re-run tools/bench_records
# and commit numbers that still clear them.
DEFAULT_RECORDS_SCALING_FLOOR = 1.2
DEFAULT_DATA_WAIT_CEILING = 0.05
# serviced trainer throughput vs the single-thread baseline: the service
# must not cost steady-state throughput; 0.9 absorbs scheduling noise on a
# CPU backend where worker threads and "device" compute share the cores
DEFAULT_SERVICE_THROUGHPUT_FLOOR = 0.9


def check_records(
    baseline: Dict,
    fresh: Optional[Dict] = None,
    *,
    wall_slack: float = DEFAULT_WALL_SLACK,
    scaling_floor: float = DEFAULT_RECORDS_SCALING_FLOOR,
    data_wait_ceiling: float = DEFAULT_DATA_WAIT_CEILING,
) -> List[Dict]:
    """RECORDS_BENCH.json gates (tools/bench_records.py output shape).

    Default mode REPLAYS the committed record (``fresh`` falls back to the
    baseline): resume bit-parity and the serviced data_wait ceiling are HARD
    (correctness/acceptance, no machine slack); worker scaling and the
    end-to-end native-vs-PIL decode ratio are dimensionless floors. A
    ``--fresh-records`` run is gated instead, with the wall-clock throughput
    additionally held to the machine-drift slack band; the decode ratio is
    only gated when the fresh host has >= 4 cores (below that the native
    decoder's one-thread floor legitimately ties/loses to PIL — the honest
    CPU floor RECORDS_BENCH documents)."""
    record = fresh if fresh is not None else baseline
    out: List[Dict] = []
    e2e = (record.get("end2end_decode") or {}).get("speedup")
    if e2e is not None and (record.get("cpu_count") or 4) >= 4:
        out.append(_finding(
            "records", "end2end_decode.speedup", 1.0, e2e,
            ">= 1.0 (native decode must not lose to PIL)", e2e >= 1.0,
        ))
    mw = record.get("multi_worker")
    if not mw:
        return out
    parity = mw.get("resume_bit_identical")
    if parity is not None:
        out.append(_finding(
            "records", "multi_worker.resume_bit_identical", True, parity,
            "== true (hard)", bool(parity),
        ))
    speedup = mw.get("speedup_best_vs_1")
    if speedup is not None:
        out.append(_finding(
            "records", "multi_worker.speedup_best_vs_1",
            scaling_floor, speedup,
            f">= {scaling_floor} (worker scaling floor)",
            speedup >= scaling_floor,
        ))
    ab = mw.get("trainer_ab") or {}
    frac = ab.get("service_data_wait_frac")
    if frac is not None:
        out.append(_finding(
            "records", "trainer_ab.service_data_wait_frac",
            data_wait_ceiling, frac,
            f"<= {data_wait_ceiling} (data_wait ~0, hard)",
            frac <= data_wait_ceiling,
        ))
    ratio = ab.get("throughput_ratio_service_over_baseline")
    if ratio is not None:
        floor = DEFAULT_SERVICE_THROUGHPUT_FLOOR
        out.append(_finding(
            "records", "trainer_ab.throughput_ratio_service_over_baseline",
            floor, ratio,
            f">= {floor} (service must not cost steady-state throughput)",
            ratio >= floor,
        ))
    if fresh is not None:
        base_mw = (baseline.get("multi_worker") or {}).get("workers") or {}
        fresh_mw = mw.get("workers") or {}
        for w, entry in base_mw.items():
            b_ips = entry.get("images_per_sec")
            f_ips = (fresh_mw.get(w) or {}).get("images_per_sec")
            if b_ips and f_ips:
                out.append(_finding(
                    "records", f"multi_worker.workers.{w}.images_per_sec",
                    b_ips, f_ips, f">= baseline / {wall_slack}",
                    f_ips >= b_ips / wall_slack,
                ))
    return out


def check_fleet(
    baseline: Dict,
    fresh: Optional[Dict] = None,
    *,
    scaling_floor: float = DEFAULT_FLEET_SCALING_FLOOR,
) -> List[Dict]:
    """Replay the BENCH_SERVE.json ``fleet`` section against its hard gates.

    The fleet soak is too heavy to re-run on every CI invocation, so the
    default mode REPLAYS the committed section (``fresh`` falls back to the
    baseline): a PR editing the serving tier must re-run ``bench_serve
    --fleet`` and commit numbers that still clear the gates — scaling floor,
    zero post-warmup recompiles on every replica, shed-with-Retry-After and
    zero non-drain 5xx past saturation, kill-soak convergence with zero lost
    accepted requests. A ``--fresh-serve`` record carrying its own ``fleet``
    section is gated instead (dimensionless, so no machine slack needed)."""
    record = fresh if fresh and fresh.get("fleet") else baseline
    fleet = record.get("fleet")
    if not fleet:
        return []
    out: List[Dict] = []
    scaling = (fleet.get("scaling") or {}).get("2") or {}
    speedup = scaling.get("speedup_vs_1")
    if speedup is not None:
        out.append(_finding(
            "fleet", "scaling.2.speedup_vs_1", scaling_floor, speedup,
            f">= {scaling_floor} (hard)", speedup >= scaling_floor,
        ))
    recompiles = sum(
        stats.get("recompiles_post_warmup", 0) or 0
        for entry in fleet.get("replica_counts", {}).values()
        for stats in (entry.get("replicas") or {}).values()
    )
    out.append(_finding(
        "fleet", "replica_post_warmup_recompiles", 0, recompiles,
        "== 0 (hard)", recompiles == 0,
    ))
    sat = fleet.get("saturation")
    if sat is not None:
        out.append(_finding(
            "fleet", "saturation.shed_with_retry_after", ">= 1",
            sat.get("shed_with_retry_after", 0), ">= 1 (structured shed)",
            sat.get("shed_with_retry_after", 0) >= 1,
        ))
        out.append(_finding(
            "fleet", "saturation.errors_5xx", 0, sat.get("errors_5xx", 0),
            "== 0 (hard)", not sat.get("errors_5xx"),
        ))
    kill = fleet.get("kill_soak")
    if kill is not None:
        out.append(_finding(
            "fleet", "kill_soak.client_errors", 0,
            kill.get("client_errors", 0), "== 0 (hard)",
            not kill.get("client_errors"),
        ))
        out.append(_finding(
            "fleet", "kill_soak.converged", True, kill.get("converged"),
            "== true (hard)", bool(kill.get("converged")),
        ))
    return out


def check_multitenant(
    baseline: Dict,
    fresh: Optional[Dict] = None,
) -> List[Dict]:
    """Replay the BENCH_SERVE.json ``multitenant`` section's hard gates.

    Like the fleet and promotion soaks, the multi-tenant soak (``bench_serve
    --multitenant``) is too heavy for every CI run, so the default mode
    REPLAYS the committed section: every tenant must have served with zero
    hard errors and a p99 inside its recorded SLO target, every replica must
    have finished with ZERO post-warmup recompiles (tenants must not trip
    each other's compilation caches), and the saturation phase must show
    weighted fair shedding — structured 429s, no 5xx, neither tenant
    starved, and the heavier tenant admitted at least the lighter one's
    share. All gates are correctness-hard (dimensionless or gated against
    the record's own SLO box), no machine slack. A ``--fresh-serve`` record
    carrying its own ``multitenant`` section is gated instead."""
    record = fresh if fresh and fresh.get("multitenant") else baseline
    mt = record.get("multitenant")
    if not mt:
        return []
    out: List[Dict] = []
    slo = mt.get("slo_p99_ms")
    for name, entry in (mt.get("models") or {}).items():
        errors = (
            entry.get("errors_5xx", 0)
            + entry.get("errors_4xx", 0)
            + entry.get("errors_conn", 0)
        )
        out.append(_finding(
            "multitenant", f"models.{name}.errors", 0, errors,
            "== 0 (hard)", errors == 0,
        ))
        out.append(_finding(
            "multitenant", f"models.{name}.ok", ">= 1",
            entry.get("ok", 0), ">= 1 (the tenant actually served)",
            entry.get("ok", 0) >= 1,
        ))
        p99 = (entry.get("latency_ms") or {}).get("p99")
        if slo is not None and p99 is not None:
            out.append(_finding(
                "multitenant", f"models.{name}.p99_ms", slo, p99,
                f"<= {slo} (the tenant's recorded SLO target)", p99 <= slo,
            ))
    recompiles = sum(
        stats.get("recompiles_post_warmup", 0) or 0
        for stats in (mt.get("replicas") or {}).values()
    )
    out.append(_finding(
        "multitenant", "replica_post_warmup_recompiles", 0, recompiles,
        "== 0 (no cross-tenant compilation leaks)", recompiles == 0,
    ))
    sat = mt.get("saturation")
    if sat is not None:
        out.append(_finding(
            "multitenant", "saturation.shed_429_total", ">= 1",
            sat.get("shed_429_total", 0), ">= 1 (structured shed)",
            sat.get("shed_429_total", 0) >= 1,
        ))
        out.append(_finding(
            "multitenant", "saturation.errors_5xx", 0,
            sat.get("errors_5xx", 0), "== 0 (hard)",
            not sat.get("errors_5xx"),
        ))
        for name, entry in (sat.get("per_model") or {}).items():
            out.append(_finding(
                "multitenant", f"saturation.{name}.ok", ">= 1",
                entry.get("ok", 0),
                ">= 1 (fair shedding must not starve a tenant)",
                entry.get("ok", 0) >= 1,
            ))
        out.append(_finding(
            "multitenant", "saturation.fair_weighted", True,
            sat.get("fair_weighted"),
            "== true (admitted shares follow the fair-share weights)",
            bool(sat.get("fair_weighted")),
        ))
    return out


# the planner acceptance floor: auto must match or beat the hand-tuned
# preset layout (ISSUE-14); dimensionless, so it replays without machine
# slack like the fleet gates
DEFAULT_PLAN_RATIO_LIMIT = 1.05


def check_plan(
    baseline: Dict,
    fresh: Optional[Dict] = None,
    *,
    ratio_limit: float = DEFAULT_PLAN_RATIO_LIMIT,
) -> List[Dict]:
    """BENCH_PLAN.json gates (bench.py --plan output shape).

    Default mode REPLAYS the committed record (like the fleet section — a PR
    touching the planner or a preset layout must re-run ``bench.py --plan``
    and commit numbers that still clear the gates): per preset, the auto
    layout's step time must stay <= ``ratio_limit`` x the hand-tuned
    layout's (dimensionless, transfers across machines), and the planner's
    predicted params+opt+stats bytes/chip must equal the placed state's
    ``tree_bytes_per_device`` EXACTLY (accounting correctness — hard). A
    ``--fresh-plan`` record is gated instead."""
    record = fresh if fresh is not None else baseline
    out: List[Dict] = []
    for name, entry in (record.get("presets") or {}).items():
        ratio = entry.get("step_time_ratio_auto_over_hand")
        if ratio is not None:
            out.append(_finding(
                "plan", f"{name}.step_time_ratio_auto_over_hand",
                ratio_limit, ratio,
                f"<= {ratio_limit} (auto matches or beats hand-tuned)",
                ratio <= ratio_limit,
            ))
        match = (entry.get("auto") or {}).get("predicted_bytes_match")
        if match is not None:
            out.append(_finding(
                "plan", f"{name}.auto.predicted_bytes_match", True, match,
                "== true (exact tree_bytes_per_device accounting, hard)",
                bool(match),
            ))
    return out


# continuous-profiling overhead: the amortized step-time ratio with a
# sparse-cadence capture landing mid-run must stay within the documented
# <= 2% budget (dimensionless, transfers across machines)
DEFAULT_PROFILE_RATIO_LIMIT = 1.02


def check_profile(
    baseline: Dict,
    fresh: Optional[Dict] = None,
    *,
    ratio_limit: float = DEFAULT_PROFILE_RATIO_LIMIT,
) -> List[Dict]:
    """BENCH_PROFILE.json gates (bench.py --profile-overhead output shape).

    Default mode REPLAYS the committed record (like plan/elastic — ci runs
    the live A/B as its own gate step, so the sentinel's job is keeping the
    committed history honest): the profiled/plain step-time ratio must clear
    the <= 2% budget, and the profiled run must have actually landed at
    least one capture inside the timed loop — a run that never captured
    would pass the ratio vacuously. ``--fresh-profile`` gates a fresh record
    instead."""
    record = fresh if fresh is not None else baseline
    out: List[Dict] = []
    ratio = record.get("step_time_ratio_profiled_over_plain")
    out.append(_finding(
        "profile", "step_time_ratio_profiled_over_plain",
        ratio_limit, ratio,
        f"<= {ratio_limit} (cadence profiling stays inside the 2% budget)",
        ratio is not None and ratio <= ratio_limit,
    ))
    captures = (record.get("profiling_on") or {}).get("captures_per_run")
    out.append(_finding(
        "profile", "profiling_on.captures_per_run", ">= 1", captures,
        ">= 1 (the profiled side must actually capture, hard)",
        captures is not None and captures >= 1,
    ))
    return out


# elastic gates: all dimensionless/hard (replay-only, like fleet/promotion —
# the full drill spawns real multi-process worlds, too heavy for every CI
# run); the downtime ceiling applies to the committed record's own box
DEFAULT_ELASTIC_DOWNTIME_CEILING_S = 120.0
DEFAULT_ELASTIC_THROUGHPUT_FLOOR = 0.4


def check_elastic(
    baseline: Dict,
    fresh: Optional[Dict] = None,
    *,
    downtime_ceiling_s: float = DEFAULT_ELASTIC_DOWNTIME_CEILING_S,
    throughput_floor: float = DEFAULT_ELASTIC_THROUGHPUT_FLOOR,
) -> List[Dict]:
    """Replay the committed BENCH_ELASTIC.json hard gates
    (tools/bench_elastic.py output shape): the headline host-death drill
    must have actually RESIZED the world (old != new, reason host_death) and
    resumed with final params BIT-IDENTICAL to a clean dp−1 run from the
    same checkpoint — the whole point of elastic training; the measured
    resize downtime must clear the ceiling and the per-chip throughput must
    survive the resize. An elastic-path PR must re-run the bench and commit
    numbers that still clear these. ``--fresh-elastic`` gates a fresh record
    instead."""
    record = fresh if fresh is not None else baseline
    out: List[Dict] = []
    out.append(_finding(
        "elastic", "bit_identical_resume", True,
        record.get("bit_identical_resume"),
        "== true (elastic resume must equal a clean same-world resume, hard)",
        bool(record.get("bit_identical_resume")),
    ))
    resize = record.get("resize") or {}
    resized = (
        resize.get("old_world") is not None
        and resize.get("old_world") != resize.get("new_world")
    )
    out.append(_finding(
        "elastic", "resize.world_changed", True,
        f"{resize.get('old_world')}->{resize.get('new_world')}",
        "old_world != new_world (the drill must actually resize, hard)",
        resized,
    ))
    out.append(_finding(
        "elastic", "resize.reason", "host_death", resize.get("reason"),
        "== host_death (the drill kills a host, hard)",
        resize.get("reason") == "host_death",
    ))
    downtime = record.get("resize_downtime_s")
    out.append(_finding(
        "elastic", "resize_downtime_s", downtime_ceiling_s, downtime,
        f"<= {downtime_ceiling_s}s (drain + re-plan + respawn)",
        downtime is not None and downtime <= downtime_ceiling_s,
    ))
    ratio = (record.get("throughput_per_chip") or {}).get("after_over_before")
    if ratio is not None:
        out.append(_finding(
            "elastic", "throughput_per_chip.after_over_before",
            throughput_floor, ratio,
            f">= {throughput_floor} (per-chip efficiency survives the "
            "resize)",
            ratio >= throughput_floor,
        ))
    redeals = record.get("data_redeals")
    if redeals is not None:
        out.append(_finding(
            "elastic", "data_redeals", ">= 1", redeals,
            ">= 1 (the resumed world re-dealt the shard assignment, hard)",
            redeals >= 1,
        ))
    return out


def check_promotion(
    baseline: Dict,
    fresh: Optional[Dict] = None,
) -> List[Dict]:
    """Replay the BENCH_SERVE.json ``promotion`` section's hard gates.

    Like the fleet soak, the promotion soak (``bench_serve --promotion``) is
    too heavy for every CI run, so the default mode REPLAYS the committed
    section: the kill-mid-canary drill must have CONVERGED (promotion
    completed, dead canary restarted) with zero client-visible errors, and
    the poisoned-candidate drill must have actually ROLLED BACK — a
    promotion pipeline whose rollback never fires is worse than none,
    because operators trust it. All gates are correctness-hard
    (dimensionless), no machine slack. A ``--fresh-serve`` record carrying
    its own ``promotion`` section is gated instead."""
    record = fresh if fresh and fresh.get("promotion") else baseline
    promo = record.get("promotion")
    if not promo:
        return []
    out: List[Dict] = []
    kill = promo.get("kill_canary")
    if kill is not None:
        out.append(_finding(
            "promotion", "kill_canary.completed", True,
            kill.get("completed"), "== true (hard)",
            bool(kill.get("completed")),
        ))
        out.append(_finding(
            "promotion", "kill_canary.converged", True,
            kill.get("converged"), "== true (hard)",
            bool(kill.get("converged")),
        ))
        out.append(_finding(
            "promotion", "kill_canary.client_errors", 0,
            kill.get("client_errors", 0), "== 0 (hard)",
            not kill.get("client_errors"),
        ))
        out.append(_finding(
            "promotion", "kill_canary.restarts", ">= 1",
            kill.get("restarts", 0),
            ">= 1 (the drill must actually have killed the canary)",
            kill.get("restarts", 0) >= 1,
        ))
    rollback = promo.get("rollback")
    if rollback is not None:
        out.append(_finding(
            "promotion", "rollback.rolled_back", True,
            rollback.get("rolled_back"),
            "== true (an injected regression MUST fire the rollback)",
            bool(rollback.get("rolled_back")),
        ))
        out.append(_finding(
            "promotion", "rollback.client_errors", 0,
            rollback.get("client_errors", 0), "== 0 (hard)",
            not rollback.get("client_errors"),
        ))
        out.append(_finding(
            "promotion", "rollback.restored", True,
            rollback.get("restored"),
            "== true (fleet back on the incumbent fingerprint)",
            bool(rollback.get("restored")),
        ))
    return out


# cold-start gates (BENCH_COLDSTART.json, tools/bench_coldstart.py): the
# warm/cold ratios are dimensionless and transfer across machines; the
# settle comparison is an absolute delta because the elastic coordinator's
# settle time is quantized by its ~2s poll interval (a ratio gate flaps on
# one tick)
DEFAULT_COLDSTART_REPLICA_RATIO = 0.5
DEFAULT_COLDSTART_RERUN_RATIO = 0.9
DEFAULT_COLDSTART_SETTLE_DELTA_S = 4.0


def check_coldstart(
    baseline: Dict,
    fresh: Optional[Dict] = None,
    *,
    max_replica_ratio: float = DEFAULT_COLDSTART_REPLICA_RATIO,
    max_rerun_ratio: float = DEFAULT_COLDSTART_RERUN_RATIO,
    settle_delta_s: float = DEFAULT_COLDSTART_SETTLE_DELTA_S,
) -> List[Dict]:
    """Replay the committed BENCH_COLDSTART.json hard gates
    (tools/bench_coldstart.py output shape). Like elastic, the drill spawns
    real multi-process worlds and full train runs — too heavy for every CI
    invocation — so the default mode REPLAYS the committed record: the
    second same-shape train run must have LEDGERED cache hits and a reduced
    time-to-first-step; a replica loading the artifact from the cache the
    first replica filled must go ready in <= half the cold time with >= 1
    hit; the elastic
    drill with ``--aot-standby`` must still resume bit-identical, must have
    actually started a standby that ended ready/superseded, and must not
    settle slower than the no-standby drill by more than the poll-quantized
    slack. A cold-start-path PR must re-run the bench and commit numbers
    that still clear these. ``--fresh-coldstart`` gates a fresh record
    instead."""
    record = fresh if fresh is not None else baseline
    out: List[Dict] = []
    rerun = record.get("train_rerun") or {}
    out.append(_finding(
        "coldstart", "train_rerun.warm_cache_hits", ">= 1",
        rerun.get("warm_cache_hits"),
        ">= 1 (second same-shape run must ledger cache hits, hard)",
        (rerun.get("warm_cache_hits") or 0) >= 1,
    ))
    ratio = rerun.get("warm_over_cold")
    out.append(_finding(
        "coldstart", "train_rerun.warm_over_cold", max_rerun_ratio, ratio,
        f"<= {max_rerun_ratio} (rerun time-to-first-step must shrink)",
        ratio is not None and ratio <= max_rerun_ratio,
    ))
    replica = record.get("replica") or {}
    out.append(_finding(
        "coldstart", "replica.warm_hits", ">= 1", replica.get("warm_hits"),
        ">= 1 (the first replica's cache must be consumed, hard)",
        (replica.get("warm_hits") or 0) >= 1,
    ))
    r_ratio = replica.get("warm_over_cold")
    out.append(_finding(
        "coldstart", "replica.warm_over_cold", max_replica_ratio, r_ratio,
        f"<= {max_replica_ratio} (warm replica time-to-ready, the "
        "ISSUE acceptance bar)",
        r_ratio is not None and r_ratio <= max_replica_ratio,
    ))
    elastic = record.get("elastic_standby") or {}
    out.append(_finding(
        "coldstart", "elastic_standby.bit_identical_resume", True,
        elastic.get("bit_identical_resume"),
        "== true (AOT standby must not perturb the resumed math, hard)",
        bool(elastic.get("bit_identical_resume")),
    ))
    sb = elastic.get("standby") or {}
    out.append(_finding(
        "coldstart", "elastic_standby.standby.started", True,
        sb.get("standby_started"),
        "== true (the drill must actually spawn a standby, hard)",
        bool(sb.get("standby_started")),
    ))
    out.append(_finding(
        "coldstart", "elastic_standby.standby.outcome",
        "ready | superseded", sb.get("standby_outcome"),
        "in (ready, superseded) — superseded means reaped at drain with "
        "its entries already on disk",
        sb.get("standby_outcome") in ("ready", "superseded"),
    ))
    out.append(_finding(
        "coldstart", "elastic_standby.standby.post_resize_cache_hits",
        ">= 1", sb.get("post_resize_cache_hits"),
        ">= 1 (the resized world must consume the standby's entries)",
        (sb.get("post_resize_cache_hits") or 0) >= 1,
    ))
    ns_settle = (elastic.get("nostandby") or {}).get("post_resize_settle_s")
    sb_settle = sb.get("post_resize_settle_s")
    if ns_settle is not None and sb_settle is not None:
        out.append(_finding(
            "coldstart", "elastic_standby.settle_delta_s",
            f"<= {settle_delta_s}", round(sb_settle - ns_settle, 3),
            f"standby settle - no-standby settle <= {settle_delta_s}s "
            "(a standby competing with the respawn instead of pre-warming "
            "it measured +6s before the drain-time reap)",
            sb_settle - ns_settle <= settle_delta_s,
        ))
    return out


DEFAULT_LOOP_CYCLE_CEILING_S = 300.0
DEFAULT_LOOP_TRIGGER_LATENCY_CEILING_S = 30.0


def check_loop(
    baseline: Dict,
    fresh: Optional[Dict] = None,
    *,
    cycle_ceiling_s: float = DEFAULT_LOOP_CYCLE_CEILING_S,
    trigger_latency_ceiling_s: float = DEFAULT_LOOP_TRIGGER_LATENCY_CEILING_S,
) -> List[Dict]:
    """Replay the committed BENCH_LOOP.json (tools/bench_loop.py) gates.

    The continuous-learning drill is too heavy for every CI run, so the
    default mode REPLAYS the committed record — and almost every gate is
    correctness-hard, not performance: the loop must have CLOSED (one cycle,
    promoted, zero rejected), with zero client-visible errors while the
    fleet flipped under live load, on a drift alert that was actually earned
    (score past threshold), retraining on data that was actually captured
    and ingested, and the whole fleet must have converged on ONE fingerprint
    — the promoted one. The two wall-clock bounds (cycle time, drift->trigger
    latency) only catch the catastrophic class, same policy as everywhere
    else. A ``--fresh-loop`` record is gated instead."""
    record = fresh if fresh else baseline
    out: List[Dict] = []
    fw = record.get("flywheel") or {}
    out.append(_finding(
        "loop", "flywheel.promoted", ">= 1", fw.get("promoted"),
        ">= 1 (the loop must actually close)",
        (fw.get("promoted") or 0) >= 1,
    ))
    out.append(_finding(
        "loop", "flywheel.rejected", 0, fw.get("rejected"),
        "== 0 (hard)", not fw.get("rejected"),
    ))
    out.append(_finding(
        "loop", "client_errors", 0, record.get("client_errors"),
        "== 0 (zero client-visible errors through the whole drill, "
        "promotion flip included)", record.get("client_errors") == 0,
    ))
    out.append(_finding(
        "loop", "client_ok", ">= 1000", record.get("client_ok"),
        ">= 1000 (the zero-errors gate must have seen real load)",
        (record.get("client_ok") or 0) >= 1000,
    ))
    ingested = record.get("samples_ingested") or 0
    out.append(_finding(
        "loop", "samples_ingested", ">= 64", ingested,
        ">= 64 (the retrain ran on actually-captured data)",
        ingested >= 64,
    ))
    out.append(_finding(
        "loop", "samples_captured", f">= ingested ({ingested})",
        record.get("samples_captured"),
        ">= samples_ingested (capture feeds ingest, never the reverse)",
        (record.get("samples_captured") or 0) >= ingested,
    ))
    alert = record.get("drift_alert") or {}
    out.append(_finding(
        "loop", "drift_alert.score", f"> {alert.get('threshold')}",
        alert.get("score"),
        "> threshold (the alert was earned, not injected)",
        alert.get("score") is not None
        and alert.get("threshold") is not None
        and alert["score"] > alert["threshold"],
    ))
    latency = record.get("drift_trigger_latency_s")
    out.append(_finding(
        "loop", "drift_trigger_latency_s",
        f"<= {trigger_latency_ceiling_s}", latency,
        "present and bounded (the flywheel saw the alert promptly)",
        latency is not None and 0 <= latency <= trigger_latency_ceiling_s,
    ))
    out.append(_finding(
        "loop", "cycle_wall_s", f"<= {cycle_ceiling_s}",
        record.get("cycle_wall_s"),
        "bounded (catastrophic-class only, like every wall-clock gate)",
        record.get("cycle_wall_s") is not None
        and record["cycle_wall_s"] <= cycle_ceiling_s,
    ))
    fingerprint = record.get("promoted_fingerprint") or ""
    mix = record.get("artifact_mix") or {}
    converged = (
        bool(fingerprint)
        and len(mix) == 1
        and next(iter(mix)).split(":", 1)[-1] in fingerprint
    )
    out.append(_finding(
        "loop", "promoted_fingerprint", "fleet converged on it",
        {"fingerprint": fingerprint[:24], "artifact_mix": mix},
        "one artifact key in the post-flip mix, matching the promoted "
        "fingerprint", converged,
    ))
    return out


# -- fresh-run plumbing ------------------------------------------------------


def _load(path: str) -> Dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def run_fresh_async(timeout: int = 900) -> Dict:
    """``bench.py --async-loop`` on the CPU shape; JSON comes via stdout."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--async-loop", "--platform=cpu"],
        capture_output=True, text=True, timeout=timeout, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    if out.returncode != 0 or not lines:
        raise RuntimeError(
            "fresh async bench failed: "
            + (out.stderr.strip().splitlines() or ["no output"])[-1][:300]
        )
    return json.loads(lines[-1])


def run_fresh_serve(out_path: str, timeout: int = 900) -> Dict:
    """``tools/bench_serve.py`` (per-request + batched A/B) on CPU."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_serve.py"),
         "--duration", "1", "--trials", "2", "--json-out", out_path],
        capture_output=True, text=True, timeout=timeout, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    if out.returncode != 0 or not os.path.exists(out_path):
        raise RuntimeError(
            "fresh serve bench failed: "
            + (out.stderr.strip().splitlines() or ["no output"])[-1][:300]
        )
    return _load(out_path)


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
    )
    parser.add_argument("--check", action="store_true",
                        help="run the comparisons and gate on them (the only "
                        "mode; the flag exists so the CI step reads as a "
                        "gate)")
    parser.add_argument("--benches",
                        default="async,serve,fleet,records,promotion,"
                        "multitenant,plan,elastic,profile,loop,coldstart,"
                        "kernels",
                        help="comma-separated subset to check")
    parser.add_argument("--baseline-async",
                        default=os.path.join(REPO, "BENCH_ASYNC.json"))
    parser.add_argument("--baseline-serve",
                        default=os.path.join(REPO, "BENCH_SERVE.json"))
    parser.add_argument("--baseline-records",
                        default=os.path.join(REPO, "RECORDS_BENCH.json"))
    parser.add_argument("--baseline-plan",
                        default=os.path.join(REPO, "BENCH_PLAN.json"))
    parser.add_argument("--baseline-elastic",
                        default=os.path.join(REPO, "BENCH_ELASTIC.json"))
    parser.add_argument("--baseline-profile",
                        default=os.path.join(REPO, "BENCH_PROFILE.json"))
    parser.add_argument("--baseline-loop",
                        default=os.path.join(REPO, "BENCH_LOOP.json"))
    parser.add_argument("--baseline-coldstart",
                        default=os.path.join(REPO, "BENCH_COLDSTART.json"))
    parser.add_argument("--fresh-coldstart", default=None, metavar="JSON",
                        help="pre-computed tools/bench_coldstart.py output "
                        "(default: replay the committed baseline's gates, "
                        "like the elastic section)")
    parser.add_argument("--coldstart-replica-ratio", type=float,
                        default=DEFAULT_COLDSTART_REPLICA_RATIO,
                        help="warm/cold replica time-to-ready ceiling on "
                        "the cold-start bench record (dimensionless; the "
                        "ISSUE acceptance bar)")
    parser.add_argument("--coldstart-rerun-ratio", type=float,
                        default=DEFAULT_COLDSTART_RERUN_RATIO,
                        help="warm/cold train time-to-first-step ceiling "
                        "on the cold-start bench record (dimensionless)")
    parser.add_argument("--fresh-loop", default=None, metavar="JSON",
                        help="pre-computed tools/bench_loop.py output "
                        "(default: replay the committed baseline's gates, "
                        "like the fleet section)")
    parser.add_argument("--loop-cycle-ceiling", type=float,
                        default=DEFAULT_LOOP_CYCLE_CEILING_S,
                        help="retrain-cycle wall-clock ceiling on the loop "
                        "bench record (seconds; catastrophic-class only)")
    parser.add_argument("--loop-trigger-latency-ceiling", type=float,
                        default=DEFAULT_LOOP_TRIGGER_LATENCY_CEILING_S,
                        help="drift-alert -> loop_trigger latency ceiling "
                        "on the loop bench record (seconds)")
    parser.add_argument("--fresh-profile", default=None, metavar="JSON",
                        help="pre-computed bench.py --profile-overhead "
                        "output (default: replay the committed baseline's "
                        "gates; ci runs the live A/B as its own step)")
    parser.add_argument("--profile-ratio-limit", type=float,
                        default=DEFAULT_PROFILE_RATIO_LIMIT,
                        help="profiled/plain step-time ratio ceiling for "
                        "the continuous-profiling bench (dimensionless; "
                        "the documented <= 2% budget)")
    parser.add_argument("--fresh-elastic", default=None, metavar="JSON",
                        help="pre-computed tools/bench_elastic.py output "
                        "(default: replay the committed baseline's gates, "
                        "like the fleet section)")
    parser.add_argument("--elastic-downtime-ceiling", type=float,
                        default=DEFAULT_ELASTIC_DOWNTIME_CEILING_S,
                        help="resize downtime ceiling on the elastic bench "
                        "record (seconds; applies to the committed record's "
                        "own box)")
    parser.add_argument("--elastic-throughput-floor", type=float,
                        default=DEFAULT_ELASTIC_THROUGHPUT_FLOOR,
                        help="floor on the elastic bench's per-chip "
                        "throughput after/before ratio")
    parser.add_argument("--fresh-plan", default=None, metavar="JSON",
                        help="pre-computed bench.py --plan output (default: "
                        "replay the committed baseline's gates, like the "
                        "fleet section)")
    parser.add_argument("--plan-ratio-limit", type=float,
                        default=DEFAULT_PLAN_RATIO_LIMIT,
                        help="auto/hand step-time ratio ceiling for the "
                        "plan bench (dimensionless; the committed record "
                        "must clear the 1.05 acceptance floor)")
    parser.add_argument("--fresh-records", default=None, metavar="JSON",
                        help="pre-computed tools/bench_records.py output "
                        "(default: replay the committed baseline's gates, "
                        "like the fleet section)")
    parser.add_argument("--fresh-async", default=None, metavar="JSON",
                        help="pre-computed bench.py --async-loop output "
                        "(skips running the bench)")
    parser.add_argument("--fresh-serve", default=None, metavar="JSON",
                        help="pre-computed tools/bench_serve.py output "
                        "(skips running the bench)")
    parser.add_argument("--wall-slack", type=float,
                        default=DEFAULT_WALL_SLACK,
                        help="multiplicative slack on absolute wall-clock "
                        "numbers (machine drift); dimensionless ratios and "
                        "hard gates ignore it")
    parser.add_argument("--async-ratio-limit", type=float,
                        default=DEFAULT_ASYNC_RATIO_LIMIT)
    parser.add_argument("--p99-slack", type=float, default=DEFAULT_P99_SLACK,
                        help="multiplicative slack on serving p99 tail "
                        "latency (the noisiest metric on shared runners; "
                        "throughput/speedup gates catch real request-path "
                        "regressions far tighter)")
    parser.add_argument("--hbm-slack", type=float, default=DEFAULT_HBM_SLACK,
                        help="multiplicative slack on the peak-HBM bench "
                        "field (near-deterministic for a fixed config, so "
                        "much tighter than wall-clock; growth-only gate)")
    parser.add_argument("--json-out", default=None)
    args = parser.parse_args(argv)

    benches = {b.strip() for b in args.benches.split(",") if b.strip()}
    findings: List[Dict] = []
    errors: List[str] = []

    if "async" in benches:
        try:
            baseline = _load(args.baseline_async)
            fresh = (
                _load(args.fresh_async)
                if args.fresh_async
                else run_fresh_async()
            )
            findings += check_async(
                baseline, fresh,
                wall_slack=args.wall_slack,
                ratio_limit=args.async_ratio_limit,
                hbm_slack=args.hbm_slack,
            )
        except (OSError, RuntimeError, ValueError,
                subprocess.TimeoutExpired) as e:
            errors.append(f"async: {e}")
    if "serve" in benches:
        try:
            baseline = _load(args.baseline_serve)
            if args.fresh_serve:
                fresh = _load(args.fresh_serve)
            else:
                # a scratch file, NOT the repo root: the fresh numbers are
                # machine-specific throwaways and must never dirty the
                # checkout (or get committed next to the real baselines)
                with tempfile.TemporaryDirectory(
                    prefix="regression_sentinel_"
                ) as tmp:
                    fresh = run_fresh_serve(
                        os.path.join(tmp, "bench_serve_fresh.json")
                    )
            findings += check_serve(
                baseline, fresh, wall_slack=args.wall_slack,
                p99_slack=args.p99_slack,
            )
        except (OSError, RuntimeError, ValueError,
                subprocess.TimeoutExpired) as e:
            errors.append(f"serve: {e}")
    if "fleet" in benches:
        try:
            baseline = _load(args.baseline_serve)
            fresh = _load(args.fresh_serve) if args.fresh_serve else None
            findings += check_fleet(baseline, fresh)
        except (OSError, ValueError) as e:
            errors.append(f"fleet: {e}")
    if "kernels" in benches:
        try:
            baseline = _load(args.baseline_serve)
            fresh = _load(args.fresh_serve) if args.fresh_serve else None
            findings += check_kernels(baseline, fresh)
        except (OSError, ValueError) as e:
            errors.append(f"kernels: {e}")
    if "promotion" in benches:
        try:
            baseline = _load(args.baseline_serve)
            fresh = _load(args.fresh_serve) if args.fresh_serve else None
            findings += check_promotion(baseline, fresh)
        except (OSError, ValueError) as e:
            errors.append(f"promotion: {e}")
    if "multitenant" in benches:
        try:
            baseline = _load(args.baseline_serve)
            fresh = _load(args.fresh_serve) if args.fresh_serve else None
            findings += check_multitenant(baseline, fresh)
        except (OSError, ValueError) as e:
            errors.append(f"multitenant: {e}")
    if "plan" in benches:
        try:
            baseline = _load(args.baseline_plan)
            fresh = _load(args.fresh_plan) if args.fresh_plan else None
            findings += check_plan(
                baseline, fresh, ratio_limit=args.plan_ratio_limit
            )
        except (OSError, ValueError) as e:
            errors.append(f"plan: {e}")
    if "elastic" in benches:
        try:
            baseline = _load(args.baseline_elastic)
            fresh = _load(args.fresh_elastic) if args.fresh_elastic else None
            findings += check_elastic(
                baseline, fresh,
                downtime_ceiling_s=args.elastic_downtime_ceiling,
                throughput_floor=args.elastic_throughput_floor,
            )
        except (OSError, ValueError) as e:
            errors.append(f"elastic: {e}")
    if "profile" in benches:
        try:
            baseline = _load(args.baseline_profile)
            fresh = _load(args.fresh_profile) if args.fresh_profile else None
            findings += check_profile(
                baseline, fresh, ratio_limit=args.profile_ratio_limit
            )
        except (OSError, ValueError) as e:
            errors.append(f"profile: {e}")
    if "loop" in benches:
        try:
            baseline = _load(args.baseline_loop)
            fresh = _load(args.fresh_loop) if args.fresh_loop else None
            findings += check_loop(
                baseline, fresh,
                cycle_ceiling_s=args.loop_cycle_ceiling,
                trigger_latency_ceiling_s=args.loop_trigger_latency_ceiling,
            )
        except (OSError, ValueError) as e:
            errors.append(f"loop: {e}")
    if "coldstart" in benches:
        try:
            baseline = _load(args.baseline_coldstart)
            fresh = (
                _load(args.fresh_coldstart) if args.fresh_coldstart else None
            )
            findings += check_coldstart(
                baseline, fresh,
                max_replica_ratio=args.coldstart_replica_ratio,
                max_rerun_ratio=args.coldstart_rerun_ratio,
            )
        except (OSError, ValueError) as e:
            errors.append(f"coldstart: {e}")
    if "records" in benches:
        try:
            baseline = _load(args.baseline_records)
            fresh = _load(args.fresh_records) if args.fresh_records else None
            findings += check_records(
                baseline, fresh, wall_slack=args.wall_slack
            )
        except (OSError, ValueError) as e:
            errors.append(f"records: {e}")

    failed = [f for f in findings if not f["ok"]]
    for f in findings:
        mark = "ok " if f["ok"] else "FAIL"
        print(
            f"[{mark}] {f['bench']}.{f['metric']}: baseline={f['baseline']} "
            f"fresh={f['fresh']} ({f['limit']})"
        )
    for e in errors:
        print(f"[ERR ] {e}", file=sys.stderr)
    verdict = {
        "ok": not failed and not errors and bool(findings),
        "checked": len(findings),
        "failed": len(failed),
        "errors": errors,
        "findings": findings,
    }
    if args.json_out:
        with open(args.json_out, "w", encoding="utf-8") as f:
            json.dump(verdict, f, indent=1)
    print(json.dumps({k: verdict[k] for k in ("ok", "checked", "failed")}))
    if not findings and not errors:
        # comparing nothing is not a pass a CI pipeline should ride on
        print("regression-sentinel: nothing compared (missing baselines?)",
              file=sys.stderr)
        return 2
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
