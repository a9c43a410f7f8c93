"""Multi-host SPMD support: process initialization and per-host data feeding.

The reference was strictly single-process/single-host — its strategy was built from
local GPUs only, with no cluster spec (reference: utils.py:6-8, model.py:114-121;
SURVEY §2.3 "Cross-host DP: NO"). The TPU-native build scales past that by design:
``jax.distributed`` brings every host's chips into one ``jax.devices()`` view, the
mesh spans them all, and XLA routes collectives over ICI within a slice and DCN
across slices. The only host-side code multi-host adds is here:

- ``initialize``: one call per process of an explicit multi-process world,
  before any jax op (a single-host run never calls it);
- ``global_shard_batch``: each process contributes ONLY its local shard of every
  global batch (``jax.make_array_from_process_local_data``), the per-host
  generalization of the reference's per-tower ``batch/n_gpus`` input_fn contract
  (reference: model.py:156-159, 298-299) — pair it with ``data.pipeline.host_shard``
  for which examples this process loads.
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tensorflowdistributedlearning_tpu.parallel.mesh import BATCH_AXIS

# Telemetry instance whose `barrier_wait` span times the multihost_utils sync
# points below (registered by the trainers for the run's lifetime). Module
# state rather than a parameter because the sync points are called from deep
# inside data/eval plumbing that has no telemetry handle — and there is at
# most one live training run per process.
_probe_telemetry = None


def instrument(telemetry) -> None:
    """Time every cross-process sync point in this module as ``telemetry``'s
    ``barrier_wait`` span. Per-window barrier-wait lands in the ledger's
    ``step_window`` events, and the fleet report (obs/fleet.py) reads the
    per-host asymmetry as straggler attribution: the slow host arrives last
    and waits ~0; everyone else's wait IS the skew."""
    global _probe_telemetry
    _probe_telemetry = telemetry


def uninstrument(telemetry=None) -> None:
    """Detach the barrier probe (pass the instance to only detach if it is
    still the registered one — a later run's probe must not be clobbered by
    an earlier run's teardown)."""
    global _probe_telemetry
    if telemetry is None or _probe_telemetry is telemetry:
        _probe_telemetry = None


@contextlib.contextmanager
def barrier_probe():
    """Span context around one multihost_utils sync point; no-op when no
    telemetry is instrumented (the single-process common case never even gets
    here — the sync points below all early-return at process_count 1)."""
    tel = _probe_telemetry
    if tel is None or not getattr(tel, "enabled", False):
        yield
        return
    from tensorflowdistributedlearning_tpu.obs.telemetry import SPAN_BARRIER

    with tel.span(SPAN_BARRIER):
        yield


def initialize(
    coordinator_address: str,
    num_processes: int,
    process_id: int,
) -> None:
    """Join this process to the jax.distributed cluster at
    ``coordinator_address`` (no-op if already initialized).

    Only ever called with an explicit world: a run that names no coordinator
    is one process driving every chip of its host and calls
    ``jax.distributed.initialize()`` not at all. jax's auto-discovery is no
    quiet no-op there — on a machine that shows TPU chips it asks the cloud
    metadata server which cluster it belongs to, which a machine without one
    answers with a stall or a connection error (timed on the chip in
    CHANGES.md, PR 21). A pod names its coordinator (``--coordinator-address
    --num-processes --process-id``).

    MUST run before any jax call that initializes the XLA backend (even
    ``jax.devices()``/``jax.process_count()``) — jax refuses to form a cluster
    afterwards. A failure to join RAISES: silently degrading to per-host
    single-process training would be wrong training at pod scale.
    """
    # already-initialized check WITHOUT touching the XLA backend
    if jax.distributed.is_initialized():
        return
    if not _platform_known_non_cpu():
        # multi-process on the CPU backend (elastic drills, the gloo
        # integration tests, laptop pods): cross-process collectives need the
        # gloo implementation selected BEFORE the backend initializes — the
        # default CPU collectives are single-process only. Applied whenever
        # the configured platform is cpu OR unset (a CPU-only machine with no
        # JAX_PLATFORMS still lands on the cpu backend); the knob only
        # affects the CPU backend, so it is inert on TPU/GPU pods.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def _platform_known_non_cpu() -> bool:
    """Whether this process is EXPLICITLY configured for a non-CPU backend,
    checked WITHOUT initializing one (the env var / jax_platforms config both
    precede backend selection). Unset means the platform is decided by what
    the machine has — which on a CPU-only host is the cpu backend."""
    import os

    platforms = os.environ.get("JAX_PLATFORMS") or ""
    try:
        platforms = jax.config.jax_platforms or platforms
    except AttributeError:
        pass
    platforms = str(platforms).lower()
    return bool(platforms) and "cpu" not in platforms


def process_info() -> Dict[str, int]:
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_device_count": jax.local_device_count(),
        "global_device_count": jax.device_count(),
    }


def per_process_batch_size(global_batch: int) -> int:
    """This process's share of every global batch (``global_batch / process_count``)
    — the per-host generalization of the reference's per-tower ``batch/n_gpus``
    split (reference: model.py:156-159)."""
    p = jax.process_count()
    if global_batch % p != 0:
        raise ValueError(
            f"Global batch size {global_batch} must be divisible by the process "
            f"count {p}"
        )
    return global_batch // p


def eval_num_batches(global_n: int, per_process_batch: int) -> int:
    """Number of eval steps EVERY process must run for a ``global_n``-example eval
    set split round-robin across processes (``data.pipeline.host_shard``).

    All processes must execute the same number of collective-bearing jitted eval
    steps or they deadlock; the largest host shard (``ceil(global_n / P)``) sets
    the count, and smaller shards pad with valid=0 batches."""
    p = jax.process_count()
    max_shard = -(-global_n // p)
    return max(1, -(-max_shard // per_process_batch))


def all_processes_max_batches(local_n: int, per_process_batch: int) -> int:
    """Equalized eval step count when each process holds its OWN record shards
    (sizes unknown globally): every process contributes ceil(local_n / batch)
    and all run the cross-process maximum, padding with valid=0 batches
    (``data.records.ClassificationRecords.batches(pad_to_batches=...)``)."""
    mine = max(1, -(-local_n // per_process_batch)) if local_n else 1
    if jax.process_count() == 1:
        return mine
    from jax.experimental import multihost_utils

    with barrier_probe():
        counts = multihost_utils.process_allgather(np.asarray(mine, np.int32))
    return int(np.max(counts))


def process_local_rows(global_batch: int, mesh: Mesh) -> np.ndarray:
    """Row indices of a batch-axis-sharded global batch owned by THIS process.

    Computed exactly from the sharding's device→index map, so it is correct for
    any device ordering. Single-process this is ``arange(global_batch)``. Use it
    to slice a batch every host holds in full (e.g. a test set) down to the local
    chunk ``global_shard_batch`` expects, and to know which output rows
    ``fetch``'s allgather attributes to which input rows."""
    sharding = NamedSharding(mesh, P(BATCH_AXIS))
    index_map = sharding.devices_indices_map((global_batch,))
    me = jax.process_index()
    rows = [
        np.arange(
            idx[0].start if idx[0].start is not None else 0,
            idx[0].stop if idx[0].stop is not None else global_batch,
        )
        for d, idx in index_map.items()
        if d.process_index == me
    ]
    return np.unique(np.concatenate(rows))


def _leaf_spec(key: Optional[str], ndim: int, spatial: bool) -> P:
    """Batch-axis spec; under spatial (sequence) parallelism ``images`` are
    additionally H-sharded over the sequence axis."""
    from tensorflowdistributedlearning_tpu.parallel.mesh import SEQUENCE_AXIS

    if spatial and key == "images":
        return P(BATCH_AXIS, SEQUENCE_AXIS, *([None] * (ndim - 2)))
    return P(BATCH_AXIS, *([None] * (ndim - 1)))


def shard_replicated_batch(tree: Any, mesh: Mesh, *, spatial: bool = False) -> Any:
    """Shard a batch dict that EVERY process holds identically in full (e.g. a
    test set built on all hosts) onto the ``batch`` (and, for images under
    ``spatial``, ``sequence``) mesh axes. Single-process this is a plain
    ``device_put``; multi-process each host contributes only the rows its devices
    own."""

    def place(key, x):
        x = np.asarray(x)
        spec = _leaf_spec(key, x.ndim, spatial)
        sharding = NamedSharding(mesh, spec)
        if jax.process_count() == 1:
            return jax.device_put(x, sharding)
        rows = process_local_rows(x.shape[0], mesh)
        return jax.make_array_from_process_local_data(sharding, x[rows])

    return {k: place(k, v) for k, v in tree.items()}


def fetch(x: Any) -> np.ndarray:
    """Device→host fetch of a batch-sharded global array that works under
    multi-host (cross-process allgather so every host sees the full array);
    single-process it is a plain ``device_get``."""
    if jax.process_count() == 1:
        return np.asarray(jax.device_get(x))
    from jax.experimental import multihost_utils

    with barrier_probe():
        return np.asarray(multihost_utils.process_allgather(x, tiled=True))


def global_shard_batch(local_tree: Any, mesh: Mesh, *, spatial: bool = False) -> Any:
    """Assemble a globally-sharded batch from THIS PROCESS's local examples.

    ``local_tree``: dict of host arrays holding only this process's
    ``global_batch / process_count`` examples (in process order — use
    ``data.pipeline.host_shard`` to pick them). Returns jax Arrays sharded on the
    ``batch`` mesh axis spanning all hosts. Single-process, this is exactly
    ``mesh_lib.shard_batch``. ``spatial`` additionally H-shards images over the
    sequence axis (multi-process spatial placement assumes each process's
    addressable devices cover whole sequence groups, as on TPU pod slices).
    """

    def place(key, x):
        x = np.asarray(x)
        sharding = NamedSharding(mesh, _leaf_spec(key, x.ndim, spatial))
        return jax.make_array_from_process_local_data(sharding, x)

    return {k: place(k, v) for k, v in local_tree.items()}
