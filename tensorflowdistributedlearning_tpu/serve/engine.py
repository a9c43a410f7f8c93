"""Bucketed-compilation inference engine: the shape-discipline layer of serving.

A TPU serving path lives or dies on two things the training stack already
learned the hard way (obs/recompile.py): every distinct input shape is its own
XLA executable, and a post-warmup compile stalls every chip for seconds. A
naive server that forwards whatever batch size arrives compiles once per
observed size — and production traffic observes *every* size. The standard
discipline (Gemma-on-TPU serving, arXiv:2605.25645 §4; TF-Serving's batching
contract) is a fixed ladder of batch **buckets**: requests are zero-padded up
to the smallest bucket that fits, so steady state touches only
``len(buckets)`` executables, all of them compiled during warmup.

``InferenceEngine`` wraps either a loaded ``jax.export`` artifact
(:meth:`from_artifact`) or any params-baked ``x -> pytree`` closure, owns the
pad → compute → slice round-trip, pre-warms every bucket, and records the
pad/compute latency split plus per-bucket hit counts into an
``obs.metrics.MetricsRegistry`` so ``/metrics`` and the serve ledger windows
report from the same instruments the trainers use.
"""

from __future__ import annotations

import bisect
import contextlib
import logging
import threading
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from tensorflowdistributedlearning_tpu.obs import trace as trace_lib
from tensorflowdistributedlearning_tpu.obs.metrics import MetricsRegistry

logger = logging.getLogger(__name__)

# the ladder production TPU servers converge on: fine steps at the small end
# (latency-sensitive singletons), coarse at the top (throughput batches)
DEFAULT_BUCKETS: Tuple[int, ...] = (1, 4, 16, 64)

# reusable no-op context: the untraced request path must not pay even the
# generator-contextmanager entry of a disabled tracer span
_NULL_CTX = contextlib.nullcontext()


class RequestTooLargeError(ValueError):
    """A request carries more examples than the largest compiled bucket —
    the caller must chunk it; silently splitting here would reorder the
    batcher's fairness guarantees."""


def _tree_map(fn, tree):
    """Apply ``fn`` to every output leaf. Dict outputs (what both tasks'
    ``predictions`` return) take a direct path — ``jax.tree_util.tree_map``
    costs ~10µs per call, which at one call per request per batch is real
    money on the request path."""
    if isinstance(tree, dict):
        return {k: fn(v) for k, v in tree.items()}
    import jax

    return jax.tree_util.tree_map(fn, tree)


class InferenceEngine:
    """Pads request batches into a fixed bucket ladder and runs ``serve_fn``.

    ``serve_fn`` maps ``x [B, *example_shape] -> pytree of arrays [B, ...]``
    with parameters baked in (exactly what ``train/serving.py`` artifacts and
    the trainers' ``serving_fn()`` closures provide). ``infer`` is thread-safe:
    registry instrument updates are GIL-atomic appends/increments, and the
    pad scratch buffers are thread-local (the single batcher worker
    materializes exactly one ladder of them).
    """

    def __init__(
        self,
        serve_fn: Callable,
        example_shape: Sequence[int],
        *,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        input_dtype="float32",
        registry: Optional[MetricsRegistry] = None,
        quantization: Optional[Dict] = None,
        tracer: Optional[trace_lib.Tracer] = None,
    ):
        self.serve_fn = serve_fn
        self.example_shape = tuple(int(d) for d in example_shape)
        self.buckets = tuple(sorted({int(b) for b in buckets}))
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError(f"buckets must be positive ints, got {buckets!r}")
        self.input_dtype = np.dtype(input_dtype)
        # manifest self-description of the artifact's precision recipe
        # (train/quantize.py section); None for raw closures / legacy
        # artifacts — informational: the graph itself carries the dtypes
        self.quantization = quantization
        self.registry = registry if registry is not None else MetricsRegistry()
        # per-request tracing (obs/trace.py): infer() emits pad/compute spans
        # that nest under the batcher's batch span; the null tracer keeps the
        # request path branch-free when tracing is off
        self.tracer = tracer if tracer is not None else trace_lib.NULL_TRACER
        self._pad_h = self.registry.histogram("serve/pad")
        self._compute_h = self.registry.histogram("serve/compute")
        # pre-create so /metrics shows the whole ladder even before traffic
        self._hit_counters = {
            b: self.registry.counter(f"serve/bucket_hits/{b}")
            for b in self.buckets
        }
        # real examples per bucket, beside the hit counts: hits*bucket vs
        # examples is the ladder's padding-waste — the utilization signal
        # that says whether the ladder fits the traffic
        self._example_counters = {
            b: self.registry.counter(f"serve/bucket_examples/{b}")
            for b in self.buckets
        }
        # per-bucket scratch pad the request path copies into instead of
        # allocating (np.concatenate allocated a fresh bucket-sized array
        # per dispatch); thread-local so concurrent infer() callers never
        # share a buffer — one worker thread (the batcher) materializes
        # exactly one ladder of buffers
        self._scratch = threading.local()
        self.warmed = False
        # buckets actually compiled so far — warmup(budget=N) may leave the
        # top of the ladder cold on purpose; a cold bucket compiles on its
        # first hit and that hit is counted (serve/cold_bucket_hits/{b})
        # so the tradeoff is visible in /metrics and the ledger
        self.warmed_buckets: set = set()
        self._cold_counters = {
            b: self.registry.counter(f"serve/cold_bucket_hits/{b}")
            for b in self.buckets
        }

    @classmethod
    def from_artifact(
        cls,
        directory: str,
        *,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        registry: Optional[MetricsRegistry] = None,
        tracer: Optional[trace_lib.Tracer] = None,
    ) -> "InferenceEngine":
        """Engine over an exported StableHLO artifact (``train/serving.py``).

        The manifest supplies the example shape and input dtype. An artifact
        exported with a FIXED batch dimension (``batch_polymorphic=False``)
        supports exactly one shape, so the ladder collapses to that single
        bucket regardless of ``buckets``.
        """
        from tensorflowdistributedlearning_tpu.train import serving as serving_lib

        serve = serving_lib.load_serving_artifact(directory)
        manifest = serving_lib.read_manifest(directory)
        shape = manifest["input_shape"]
        if any(d is None for d in shape[1:]):
            raise ValueError(
                f"artifact input shape {shape} has a symbolic non-batch dim — "
                "the engine needs static example shapes to pad against"
            )
        if shape[0] is not None:
            logger.info(
                "artifact %s was exported with fixed batch %d — bucket ladder "
                "collapses to that single bucket", directory, shape[0],
            )
            buckets = (int(shape[0]),)
        return cls(
            serve,
            tuple(shape[1:]),
            buckets=buckets,
            # read_manifest applied the legacy float32 default (and rejected
            # corrupt quantization metadata) — the engine just consumes
            input_dtype=manifest["input_dtype"],
            registry=registry,
            quantization=manifest.get("quantization"),
            tracer=tracer,
        )

    @property
    def compute_dtype(self) -> Optional[str]:
        """The manifest-declared matmul/conv arithmetic dtype
        (``quantization.compute_dtype``, read_manifest-defaulted): "int8"
        when the graph was traced through the quantized-compute kernels,
        the float dtype for dequantize-in-graph/plain artifacts, ``None``
        for raw closures with no quantization section. Informational — the
        exported graph carries its own arithmetic; this is how telemetry
        and the quantize-check gate know which budget applies."""
        if not self.quantization:
            return None
        return self.quantization.get("compute_dtype")

    @property
    def max_batch_size(self) -> int:
        return self.buckets[-1]

    @property
    def bucket_hits(self) -> Dict[int, int]:
        return {
            b: self.registry.counter(f"serve/bucket_hits/{b}").value
            for b in self.buckets
        }

    @property
    def padding_waste(self) -> Dict[int, float]:
        """Per-bucket fraction of compiled batch slots filled with padding:
        ``1 - examples / (hits * bucket)``. Only buckets that saw traffic
        appear — 32-client closed-loop traffic all landing in bucket 64
        shows up as waste 0.5 there (at most 32 live rows per compiled
        64-slot batch), an all-singletons pattern through bucket 4 as
        waste 0.75."""
        waste: Dict[int, float] = {}
        for b in self.buckets:
            hits = self._hit_counters[b].value
            if hits:
                examples = self._example_counters[b].value
                waste[b] = round(1.0 - examples / (hits * b), 4)
        return waste

    def _scratch_for(self, bucket: int) -> np.ndarray:
        bufs = getattr(self._scratch, "bufs", None)
        if bufs is None:
            bufs = self._scratch.bufs = {}
        buf = bufs.get(bucket)
        if buf is None or buf.dtype != self.input_dtype:
            # allocated in the ARTIFACT's wire dtype, never a float32
            # default: an int8/bf16-input artifact padding through a f32
            # scratch would silently upcast (and re-cast) every request
            # batch before dispatch. The dtype recheck keeps a cached
            # ladder from going stale if input_dtype is ever rebound.
            buf = bufs[bucket] = np.zeros(
                (bucket, *self.example_shape), self.input_dtype
            )
        return buf

    def select_bucket(self, n: int) -> int:
        """Smallest bucket that fits ``n`` examples."""
        if n < 1:
            raise ValueError(f"cannot serve an empty batch (n={n})")
        i = bisect.bisect_left(self.buckets, n)
        if i == len(self.buckets):
            raise RequestTooLargeError(
                f"{n} examples exceeds the largest bucket "
                f"({self.max_batch_size}); chunk the request"
            )
        return self.buckets[i]

    def warmup(
        self,
        telemetry=None,
        *,
        budget: Optional[int] = None,
        mark_warm: bool = True,
    ) -> Dict[int, float]:
        """Compile the bucket ladder up front (zeros input), returning
        per-bucket wall seconds. After this, steady-state serving touches
        only warmed shapes — when ``telemetry`` is passed, its recompile
        detector is marked warm so any later compile is flagged (and
        ledgered) as the goodput bug it is.

        ``budget`` caps how many buckets are compiled, smallest first (the
        registry's ``prewarm_budget`` / ``serve --prewarm-buckets`` knob):
        spawn-to-ready time trades against a first-request compile stall on
        each cold bucket. Cold buckets are excluded from the recompile
        detector's warm mark only in the sense that their first hit is
        ledgered per bucket (``serve/cold_bucket_hits/{b}``).

        ``mark_warm=False`` defers arming the recompile detector: a replica
        loading SEVERAL engines (multi-tenant registry load) warms them in
        sequence and must mark warm once, after the LAST — otherwise every
        engine after the first would be flagged as a steady-state
        recompile.

        Buckets compile CONCURRENTLY (XLA releases the GIL for the whole
        backend compile) after the smallest bucket compiles alone — the
        first-ever call through a loaded Exported must not race itself (see
        the comment below), so ladder warmup costs ~smallest + slowest
        instead of the sum. Each bucket joins ``warmed_buckets`` as its own
        compile lands, and the detector's warm mark still happens strictly
        after every bucket — the ordering contract is unchanged."""
        import jax

        to_warm = self.buckets
        if budget is not None and budget < len(self.buckets):
            to_warm = self.buckets[: max(0, int(budget))]
        timings: Dict[int, float] = {}

        def _compile(b: int) -> float:
            # transient zeros: the request-path scratch pads are thread-local
            # and the batcher worker is a different thread than the one
            # running warmup — filling this thread's ladder would just leave
            # a dead duplicate alive for the engine's lifetime
            x = np.zeros((b, *self.example_shape), self.input_dtype)
            t0 = time.perf_counter()
            jax.block_until_ready(self.serve_fn(x))
            return round(time.perf_counter() - t0, 6)

        if to_warm:
            # The FIRST call must be alone: jax caches the jitted wrapper
            # around a loaded Exported under an lru keyed on the exported
            # object, and concurrent first-ever calls race its miss path —
            # each builds its own wrapper, the bucket executables split
            # across them, and only one wrapper survives in the cache. The
            # survivor is then missing the other threads' shapes, so the
            # first request on a "lost" bucket recompiles AFTER the warm
            # mark — the exact goodput bug warmup exists to prevent
            # (surfaced as a flaky post-warmup recompile under the full
            # test sweep). Warming the smallest bucket synchronously
            # populates the cache entry; the remaining buckets then share
            # the one wrapper and still overlap their compiles.
            timings[to_warm[0]] = _compile(to_warm[0])
            self.warmed_buckets.add(to_warm[0])
        rest = to_warm[1:]
        if len(rest) > 1:
            from concurrent.futures import ThreadPoolExecutor, as_completed

            with ThreadPoolExecutor(
                max_workers=len(rest), thread_name_prefix="warmup"
            ) as pool:
                futures = {pool.submit(_compile, b): b for b in rest}
                for fut in as_completed(futures):
                    b = futures[fut]
                    timings[b] = fut.result()
                    self.warmed_buckets.add(b)
        else:
            for b in rest:
                timings[b] = _compile(b)
                self.warmed_buckets.add(b)
        self.warmed = True
        if telemetry is not None:
            warm_fields = {}
            if self.quantization is not None:
                warm_fields["serving_dtype"] = self.quantization.get("dtype")
                if self.quantization.get("compute_dtype"):
                    warm_fields["compute_dtype"] = self.quantization[
                        "compute_dtype"
                    ]
            cold = [b for b in self.buckets if b not in self.warmed_buckets]
            if cold:
                warm_fields["cold_buckets"] = [str(b) for b in cold]
                warm_fields["prewarm_budget"] = len(to_warm)
            telemetry.event(
                "serve_warmup",
                buckets={str(b): timings[b] for b in sorted(timings)},
                example_shape=list(self.example_shape),
                input_dtype=str(self.input_dtype),
                **warm_fields,
            )
            if mark_warm:
                telemetry.mark_warm()
            # bucket compilation is the serving tier's peak-HBM moment on
            # most artifacts — ledger it as the compile-phase watermark
            # before request traffic attributes anything to "infer"
            sample = getattr(telemetry, "sample_watermark", None)
            if sample is not None:
                from tensorflowdistributedlearning_tpu.obs import (
                    capacity as capacity_lib,
                )

                sample(capacity_lib.PHASE_COMPILE)
        return timings

    def infer(self, x) -> Dict:
        """Forward ``x [n, *example_shape]`` through the bucket ladder: pad to
        the selected bucket, run, slice every output back to ``n`` rows."""
        import jax

        x = np.asarray(x, self.input_dtype)
        if x.shape[1:] != self.example_shape:
            raise ValueError(
                f"expected examples of shape {self.example_shape}, "
                f"got batch {x.shape}"
            )
        n = x.shape[0]
        bucket = self.select_bucket(n)
        if self.warmed and bucket not in self.warmed_buckets:
            # cold bucket past a budgeted warmup: this dispatch pays the
            # compile. Count it (per bucket) and fold the bucket into the
            # warmed set — the executable is cached from here on.
            self._cold_counters[bucket].inc()
            self.warmed_buckets.add(bucket)
        # trace spans nest under the caller's active span (the batcher's
        # batch span) via the tracer's thread-local stack; disabled tracing
        # costs one attribute read per infer
        traced = self.tracer.enabled
        attrs = {"bucket": bucket, "n": n} if traced else None
        t0 = time.perf_counter()
        with (
            self.tracer.span(trace_lib.SPAN_PAD, attrs=attrs)
            if traced
            else _NULL_CTX
        ):
            if n != bucket:
                # copy into the bucket's reusable scratch pad (zeroing the
                # tail, which may hold rows from a previous, fuller dispatch)
                # instead of concatenating into a fresh allocation every
                # call. infer() blocks until the device result is ready
                # before returning, so within a thread the buffer is never
                # overwritten mid-compute.
                buf = self._scratch_for(bucket)
                buf[:n] = x
                buf[n:] = 0
                x = buf
        self._pad_h.record(time.perf_counter() - t0)
        t0 = time.perf_counter()
        with (
            self.tracer.span(trace_lib.SPAN_COMPUTE, attrs=attrs)
            if traced
            else _NULL_CTX
        ):
            out = jax.block_until_ready(self.serve_fn(x))
        self._compute_h.record(time.perf_counter() - t0)
        self._hit_counters[bucket].inc()
        self._example_counters[bucket].inc(n)
        return _tree_map(lambda a: np.asarray(a)[:n], out)
