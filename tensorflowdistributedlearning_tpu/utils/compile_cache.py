"""Persistent XLA compile-cache plumbing: the load-not-compile layer.

Compilation is the biggest cold-start cliff in the stack: every serve
replica recompiles its bucket ladder, every elastic resize recompiles the
step function at the new world size, every train restart pays full warmup
(the flagship train step alone is about a minute of XLA:TPU compile). JAX
ships a content-addressed persistent compilation cache; this module decides
WHERE it lives, once, for every entry point, and turns its hit/miss stream
into telemetry the rest of obs/ can ledger.

Placement (:func:`resolve`, applied by :func:`configure`), first match wins:

1. ``JAX_COMPILATION_CACHE_DIR`` in the environment — the operator placed
   the cache, and then no code sets another (``--compile-cache-dir`` is
   ignored with a log line);
2. the ``--compile-cache-dir`` flag / ``TrainConfig.compile_cache_dir``;
3. one fixed directory inside the checkout, :data:`DEFAULT_DIR` — unless
   the process is pinned to the CPU backend, where there is no default:
   XLA:CPU executables are machine-feature-sensitive (entries written on
   another host warn on load and can SIGILL) and resumed resilience
   children have crashed inside their serialization, so a CPU run caches
   only where it is told to.

The path is part of what a deployment keys on — a directory that moves
never hits — so there is no temp-dir fallback anywhere: a resolved
directory that cannot be written is an error (:class:`CompileCacheError`),
not an uncached run.

Whichever way the directory was chosen, :func:`configure` forces the
cache-everything knobs (JAX's defaults skip sub-second compiles and small
entries) and registers the hit/miss listeners:

- :func:`consume_pending` is called by ``obs.recompile`` exactly once per
  backend-compile event to learn whether that compile was served from the
  cache (and how much compile time the hit saved). JAX fires the cache-hit
  monitoring events synchronously on the compiling thread *before* the
  compile-duration event closes, so a thread-local carries the verdict
  across the two listener callbacks.
- :func:`stats` gives the process-wide hit/miss counters.

Cache-key caveat (documented, load-bearing): keys hash the canonicalized
module, jaxlib version, registered XLA flags, compile options AND the
serialized backend topology — which is PROCESS-LOCAL: it covers the total
device count and which devices belong to this process, so two processes
only share entries when their whole topology matches rank-for-rank
(verified empirically: rank 0 and rank 1 of the same 2-process world
compute *different* keys for the same module). Consequences wired through
this codebase: (1) the elastic AOT standby is a real (world-1)-process
mini-world, not a solo emulator; (2) a serving ladder is compiled by the
first server that loads the artifact, under the serving topology, and
every later start on that machine loads it — an exporter that sees other
devices than the server could not have produced matching entries;
(3) ``configure`` disables the XLA autotune-cache debug option, whose
directory (a path inside cache_dir) would otherwise be hashed into every
key, pinning entries to one absolute cache path; (4) ``configure`` keys
entries on the module's metadata too (scopes, source lines): a loaded
program carries the metadata it was compiled with, and the step programs'
scope maps (``obs/scopes.py``) are read from it, so an entry compiled by a
commit with other scopes or lines must not be found. Keys do NOT survive
jaxlib upgrades or XLA flag changes.
"""

from __future__ import annotations

import logging
import os
import tempfile
import threading
from typing import Dict, Optional, Tuple

import jax
from jax import monitoring as _monitoring
from jax.experimental.compilation_cache import compilation_cache as jax_cache

logger = logging.getLogger(__name__)

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# the one fixed directory: the checkout root (git-ignored), so every entry
# point started from this tree — the CLI, chip_smoke.py, bench.py — finds
# what the previous one compiled
DEFAULT_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache_tpu",
)


class CompileCacheError(RuntimeError):
    """The resolved cache directory cannot be used. Raised, not logged: a run
    that silently pays every compile in full looks like a slow device."""


# jax.monitoring event names fired by jax._src.compiler.compile_or_get_cached
# (verified against the installed jax; literal strings are the stable API)
_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"

_lock = threading.Lock()
_listener_registered = False

# Per-thread in-flight verdict: compile_or_get_cached fires request → (hit,
# saved) → the backend-compile duration event, all on the compiling thread,
# so thread-local state bridges them without cross-compile races even under
# the parallel warmup pool.
_tls = threading.local()

# Process-wide counters (updated by the listeners on every compile) for
# introspection and the run_end summary; guarded by _lock. "misses" is
# derived as requests - hits at stats() read time.
_stats: Dict[str, float] = {"requests": 0, "hits": 0, "saved_s": 0.0}


def _on_record_event(event: str, **kwargs) -> None:
    # Stats are counted here, in the listener, not in consume_pending():
    # consume_pending() only runs when an obs.recompile detector is attached,
    # and a bare process (serve replica without telemetry, standby sidecar)
    # must still report accurate hit/miss counts via stats().
    if event == _REQUEST_EVENT:
        _tls.pending_request = True
        with _lock:
            _stats["requests"] += 1
    elif event == _HIT_EVENT:
        _tls.pending_hit = True
        with _lock:
            _stats["hits"] += 1


def _on_duration_event(event: str, duration_secs: float, **kwargs) -> None:
    if event == _SAVED_EVENT:
        _tls.pending_saved_s = float(duration_secs)
        with _lock:
            _stats["saved_s"] += float(duration_secs)


def _ensure_listeners() -> None:
    """Register the cache-hit monitoring listeners once per process."""
    global _listener_registered
    with _lock:
        if _listener_registered:
            return
        _monitoring.register_event_listener(_on_record_event)
        _monitoring.register_event_duration_secs_listener(_on_duration_event)
        _listener_registered = True


def consume_pending() -> Tuple[Optional[bool], float]:
    """Pop this thread's in-flight cache verdict.

    Returns ``(cache_hit, saved_s)`` where ``cache_hit`` is ``None`` when
    the persistent cache was not consulted for the compile that just closed
    (cache disabled, or key generation failed), ``True`` on a hit (with the
    compile time the hit saved), ``False`` on a genuine miss. Called by
    ``obs.recompile._dispatch`` exactly once per backend-compile event.
    """
    requested = getattr(_tls, "pending_request", False)
    hit = getattr(_tls, "pending_hit", False)
    saved_s = getattr(_tls, "pending_saved_s", 0.0)
    _tls.pending_request = False
    _tls.pending_hit = False
    _tls.pending_saved_s = 0.0
    if not requested:
        return None, 0.0
    return (True, saved_s) if hit else (False, 0.0)


def stats() -> Dict[str, float]:
    """Process-wide hit/miss counters (every compile the listeners saw)."""
    with _lock:
        out = dict(_stats)
    out["misses"] = out["requests"] - out["hits"]
    return out


def reset_stats() -> None:
    with _lock:
        for k in _stats:
            _stats[k] = 0.0 if k == "saved_s" else 0


def active_dir() -> Optional[str]:
    """The directory jax's persistent cache writes to (None = off). Read from
    jax's own config, so it is true however the directory was set."""
    return jax.config.jax_compilation_cache_dir or None


def _cpu_pinned() -> bool:
    """Whether this process is pinned to the CPU backend, decided WITHOUT
    initializing one (controllers that spawn chip users call configure too)."""
    platforms = jax.config.jax_platforms or os.environ.get("JAX_PLATFORMS")
    return (platforms or "").strip().lower() == "cpu"


def resolve(flag_dir: Optional[str] = None) -> Tuple[Optional[str], str]:
    """Where this process's compile cache goes: ``(directory, source)`` with
    source ``env`` | ``flag`` | ``default`` | ``off`` (directory None)."""
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        return os.path.abspath(os.path.expanduser(env_dir)), "env"
    if flag_dir:
        return os.path.abspath(os.path.expanduser(flag_dir)), "flag"
    if _cpu_pinned():
        return None, "off"
    return DEFAULT_DIR, "default"


def _probe_writable(cache_dir: str) -> None:
    try:
        os.makedirs(cache_dir, exist_ok=True)
        fd, probe = tempfile.mkstemp(prefix=".cache_probe_", dir=cache_dir)
        os.close(fd)
        os.unlink(probe)
    except OSError as e:
        raise CompileCacheError(
            f"compile cache dir {cache_dir} is not writable ({e}); place the "
            f"cache with {ENV_VAR} or --compile-cache-dir"
        ) from e


def configure(flag_dir: Optional[str] = None) -> Optional[str]:
    """Resolve the cache directory (:func:`resolve`) and point this process's
    XLA compiles at it; returns the directory, or None when the cache is off
    (a CPU-pinned run that named none).

    Every entry point calls this once, before its first compile. Safe at any
    later point too, and idempotent: an already-initialized cache backend is
    reset only when the directory changes. Raises :class:`CompileCacheError`
    when the directory cannot be written.
    """
    cache_dir, source = resolve(flag_dir)
    if cache_dir is None:
        return None
    if source == "env" and flag_dir:
        logger.info(
            "%s=%s places the compile cache; ignoring --compile-cache-dir %s",
            ENV_VAR, cache_dir, flag_dir,
        )
    _probe_writable(cache_dir)
    changed = active_dir() != cache_dir or not _listener_registered
    # env placement: jax read the variable itself at import; writing the
    # absolute form back only normalizes a relative path
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache EVERYTHING: the defaults (min 1.0s compile, min entry size) skip
    # the small programs around the step (init, augmentation, metric merges)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # The default enables the XLA per-fusion autotune cache, whose
    # directory (a path INSIDE cache_dir) is baked into compile options
    # and is NOT stripped from the cache key — so keys would depend on
    # the cache dir's absolute path. Disable it; it's a GPU-only feature.
    jax.config.update("jax_persistent_cache_enable_xla_caches", "none")
    # By default the key strips an op's metadata, so a program loaded from
    # the cache carries the metadata of whichever commit compiled it first —
    # and a step program's scope map (obs/scopes.py) is read from exactly
    # that. Keyed on it, a program is loaded only where its scopes and source
    # lines are this commit's: restarts of one commit hit as before, and a
    # commit that moves the model's lines compiles once for itself.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if changed:
        # The cache backend latches on first compile — even when the dir
        # was unset, leaving it off permanently — so a configure() that
        # lands after one must reset it for the directory to take.
        jax_cache.reset_cache()
        logger.info("persistent compile cache at %s (%s)", cache_dir, source)
    _ensure_listeners()
    return cache_dir
