"""Root conftest: force an 8-device CPU platform BEFORE the jax backend initializes, so
every multi-device test runs the real sharded code path without TPU hardware (the
fake-backend layer the reference lacked — SURVEY §4).
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: the test suite's wall time is dominated by XLA
# compiles of the shard_map-ped train/eval/predict steps; caching them across runs
# cuts repeat-suite time by minutes. Keyed by HLO hash, so stale entries are
# impossible — only disk space is spent. TFDL_NO_COMPILE_CACHE=1 opts out:
# XLA:CPU AOT serialization is machine-feature-sensitive (entries written on a
# different host warn on load and can SIGILL) and one serialization segfault
# inside jax's put_executable_and_time was observed on a 1-core driver box —
# when the cache misbehaves, correctness beats repeat-run speed.
# The suite sets its cache here and not through utils/compile_cache.configure:
# that also registers the hit/miss listeners, which would add cache verdicts
# to every ledger the tests read. A cache placed from outside
# (JAX_COMPILATION_CACHE_DIR) is left where it was put.
if not os.environ.get("TFDL_NO_COMPILE_CACHE"):
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        _cache_dir = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), ".jax_cache"
        )
        jax.config.update("jax_compilation_cache_dir", _cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


# Long full-suite runs accumulate hundreds of live XLA:CPU executables in one
# process; on a small (1-core) driver box this has produced a deterministic
# SEGFAULT inside backend_compile_and_load around test ~315 (the compiler
# itself crashing, not a test) while every module passes in isolation.
# Dropping the in-memory jit caches between modules once the process has grown
# past a threshold bounds that accumulation; the occasional recompile is noise
# next to a crashed suite.
import pytest  # noqa: E402

# clear when RSS has GROWN this much since the last clear (not an absolute
# threshold: clear_caches frees heap that glibc never returns to the OS, so
# absolute RSS stays high after a clear and would re-trigger on every test,
# recompiling the whole suite tail)
_RSS_GROWTH_CLEAR_BYTES = 5 << 30
_rss_floor = [0]


@pytest.fixture(autouse=True)
def _bound_live_executables():
    yield
    try:
        import psutil

        rss = psutil.Process().memory_info().rss
    except Exception:
        return
    if _rss_floor[0] == 0:
        _rss_floor[0] = rss
    if rss - _rss_floor[0] > _RSS_GROWTH_CLEAR_BYTES:
        jax.clear_caches()
        _rss_floor[0] = psutil.Process().memory_info().rss
