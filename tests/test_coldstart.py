"""Cold-start elimination: persistent compile cache + load-not-compile.

The contracts under test are the ones the cold-start work ships on:

- the persistent compile cache survives the PROCESS — a fresh interpreter
  running the same-shape computation loads its executables (ledgered cache
  hits, zero real compiles) instead of rebuilding them;
- ONE resolver places the cache (utils/compile_cache.py): the environment's
  JAX_COMPILATION_CACHE_DIR beats the flag beats the checkout's fixed
  directory, a CPU-pinned run has no default, nothing ever lands in a temp
  dir, and a directory that cannot be written is an error;
- the first server to load an artifact compiles its bucket ladder into that
  cache and every later start loads it;
- parallel bucket warmup preserves the warm-mark ordering and the
  ``warmed_buckets`` accounting;
- ``replica_ready.time_to_ready_s`` and the compile-cache verdicts surface
  in ``telemetry-report``/``telemetry-top``, with cache-served compiles
  counted apart from real recompiles (the zero-post-warmup contract stays
  meaningful under a shared cache).
"""

import json
import os
import stat
import subprocess
import sys

import numpy as np
import pytest

from tensorflowdistributedlearning_tpu import obs
from tensorflowdistributedlearning_tpu.obs.report import (
    build_report,
    render_report,
)
from tensorflowdistributedlearning_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FEATURES = 6
CLASSES = 3


def _env(extra=None):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
    )
    env.update(extra or {})
    return env


# -- cross-process persistent-cache round-trip -------------------------------

_ROUNDTRIP_SCRIPT = """
import json, sys
sys.path.insert(0, {repo!r})
import numpy as np
from tensorflowdistributedlearning_tpu.utils import compile_cache
from tensorflowdistributedlearning_tpu.obs import Telemetry

assert compile_cache.configure({cache_dir!r})
import jax, jax.numpy as jnp

tel = Telemetry({workdir!r}, run_info={{"kind": "cache-roundtrip"}})

@jax.jit
def f(x):
    return jnp.tanh(x @ x.T).sum()

@jax.jit
def g(x):
    return (x * 2.0 + 1.0).mean()

jax.block_until_ready(f(jnp.ones((8, 8))))
jax.block_until_ready(g(jnp.ones((16,))))
tel.close()
print(json.dumps(compile_cache.stats()))
"""


@pytest.fixture(scope="module")
def cache_roundtrip(tmp_path_factory):
    """Two fresh interpreters, same cache dir, same computation — the
    second must LOAD. Shared by the ledger and report assertions."""
    base = tmp_path_factory.mktemp("cc_roundtrip")
    cache_dir = str(base / "cache")
    runs = []
    for i in (0, 1):
        workdir = str(base / f"run{i}")
        script = _ROUNDTRIP_SCRIPT.format(
            repo=REPO, cache_dir=cache_dir, workdir=workdir
        )
        out = subprocess.run(
            [sys.executable, "-c", script], env=_env(), capture_output=True,
            text=True, timeout=240,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        stats = json.loads(out.stdout.strip().splitlines()[-1])
        runs.append({"workdir": workdir, "stats": stats})
    return cache_dir, runs


def test_second_interpreter_loads_from_cache(cache_roundtrip):
    cache_dir, (cold, warm) = cache_roundtrip
    # run 0 populated the cache (misses), run 1 consumed it (hits, 0 misses)
    assert cold["stats"]["misses"] >= 2 and cold["stats"]["hits"] == 0
    assert warm["stats"]["hits"] >= 2 and warm["stats"]["misses"] == 0
    assert len(os.listdir(cache_dir)) >= 2


_SCOPED_SCRIPT = """
import json, sys
sys.path.insert(0, {repo!r})
from tensorflowdistributedlearning_tpu.utils import compile_cache

assert compile_cache.configure({cache_dir!r})
import jax, jax.numpy as jnp

@jax.jit
def f(x):
    with jax.named_scope({scope!r}):
        return jnp.tanh(x @ x.T).sum()

x = jnp.ones((8, 8))
jax.block_until_ready(f(x))
text = f.lower(x).compile().as_text()
print(json.dumps(dict(compile_cache.stats(), names=[s for s in ("scope_a", "scope_b") if s in text])))
"""


def test_a_program_under_other_scopes_is_not_loaded(tmp_path):
    """A loaded program carries the metadata it was compiled with, and the
    step programs' scope maps (obs/scopes.py) are read from it: the cache is
    keyed on metadata, so the same computation under another scope compiles
    for itself, and under the same one still loads."""
    cache_dir = str(tmp_path / "cache")
    runs = []
    for scope in ("scope_a", "scope_a", "scope_b"):
        script = _SCOPED_SCRIPT.format(repo=REPO, cache_dir=cache_dir, scope=scope)
        out = subprocess.run(
            [sys.executable, "-c", script], env=_env(), capture_output=True,
            text=True, timeout=240,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    first, again, other = runs
    assert first["hits"] == 0 and first["misses"] >= 1
    assert again["hits"] >= 1 and again["misses"] == 0
    assert other["misses"] >= 1  # the program itself; what is around it may load
    assert [r["names"] for r in runs] == [["scope_a"], ["scope_a"], ["scope_b"]]


def test_cache_verdicts_reach_the_ledger(cache_roundtrip):
    _, (cold, warm) = cache_roundtrip
    cold_events = obs.read_ledger(cold["workdir"])
    warm_events = obs.read_ledger(warm["workdir"])

    def compiles(events):
        return [e for e in events if e.get("event") == "compile"]

    # cache-consulted compiles are ALWAYS ledgered (the duration threshold
    # would hide exactly the proof the cache works)
    assert any(e.get("cache_hit") is False for e in compiles(cold_events))
    warm_hits = [e for e in compiles(warm_events) if e.get("cache_hit")]
    assert warm_hits, "second run ledgered no cache hits"
    # the second run did strictly fewer REAL compiles than the first
    real = lambda evs: [e for e in compiles(evs) if not e.get("cache_hit")]
    assert len(real(warm_events)) < len(real(cold_events))
    # run_end totals carry the detector's exact counters
    warm_end = [e for e in warm_events if e.get("event") == "run_end"][-1]
    assert warm_end["compile_cache_hits"] >= 2
    assert warm_end["compile_cache_misses"] == 0


def test_report_renders_hit_ratio(cache_roundtrip):
    _, (_, warm) = cache_roundtrip
    report = build_report(warm["workdir"])
    cc = report["compile_cache"]
    assert cc["hits"] >= 2 and cc["misses"] == 0
    assert cc["hit_ratio"] == 1.0
    text = render_report(report)
    assert "compile cache:" in text
    assert "100% served from cache" in text


# -- placement: one resolver, env > flag > fixed dir ---------------------------


@pytest.fixture
def accelerator_run(monkeypatch):
    """A process not pinned to the CPU, with no cache placed from outside."""
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.setattr(compile_cache, "_cpu_pinned", lambda: False)


def test_resolver_env_beats_flag_beats_fixed_dir(
    accelerator_run, monkeypatch, tmp_path
):
    assert compile_cache.resolve() == (compile_cache.DEFAULT_DIR, "default")
    assert compile_cache.DEFAULT_DIR == os.path.join(REPO, ".jax_cache_tpu")
    flag = str(tmp_path / "flag")
    assert compile_cache.resolve(flag) == (flag, "flag")
    env = str(tmp_path / "env")
    monkeypatch.setenv(compile_cache.ENV_VAR, env)
    assert compile_cache.resolve(flag) == (env, "env")
    assert compile_cache.resolve() == (env, "env")


def test_cpu_pinned_run_has_no_default_cache(monkeypatch, tmp_path):
    """The suite itself runs CPU-pinned: no flag, no env -> no cache (XLA:CPU
    entries are machine-feature-sensitive), and configure() is a no-op."""
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.resolve() == (None, "off")
    before = compile_cache.active_dir()
    assert compile_cache.configure(None) is None
    assert compile_cache.active_dir() == before
    # ...but a CPU run still caches where it is told to
    flag = str(tmp_path / "flag")
    assert compile_cache.resolve(flag) == (flag, "flag")


def test_unwritable_named_cache_dir_is_an_error(monkeypatch, tmp_path):
    """A directory named from outside that cannot be written raises — an
    uncached run looks like a slow device. (A path under a regular FILE
    cannot be created even by root.)"""
    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    before = compile_cache.active_dir()
    for placed_by_env in (False, True):
        target = str(blocker / "cache")
        if placed_by_env:
            monkeypatch.setenv(compile_cache.ENV_VAR, target)
        else:
            monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        with pytest.raises(compile_cache.CompileCacheError, match="writable"):
            compile_cache.configure(None if placed_by_env else target)
    assert compile_cache.active_dir() == before  # untouched


_ENV_PLACED_SCRIPT = """
import json, os, sys
sys.path.insert(0, {repo!r})
from tensorflowdistributedlearning_tpu.utils import compile_cache
placed = compile_cache.configure({flag!r})
import jax, jax.numpy as jnp
jax.block_until_ready(jax.jit(lambda x: jnp.tanh(x) * 3.0)(jnp.ones((4,))))
print(json.dumps({{
    "placed": placed,
    "jax_dir": jax.config.jax_compilation_cache_dir,
    "min_compile_s": jax.config.jax_persistent_cache_min_compile_time_secs,
    "stats": compile_cache.stats(),
}}))
"""


def test_env_placed_cache_gets_the_listeners_and_knobs(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set from outside: every entry lands there,
    the flag is ignored, and the hit/miss listeners and cache-everything
    knobs apply all the same."""
    env_dir, flag_dir = str(tmp_path / "env"), str(tmp_path / "flag")
    script = _ENV_PLACED_SCRIPT.format(repo=REPO, flag=flag_dir)
    out = subprocess.run(
        [sys.executable, "-c", script],
        env=_env({compile_cache.ENV_VAR: env_dir}),
        capture_output=True, text=True, timeout=240,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["placed"] == env_dir and res["jax_dir"] == env_dir
    assert res["min_compile_s"] == 0.0  # a sub-second compile was cached
    assert res["stats"]["misses"] >= 1  # the listeners saw it
    assert os.listdir(env_dir) and not os.path.exists(flag_dir)


# -- the first server compiles the ladder, every later start loads it ---------


@pytest.fixture(scope="module")
def serve_fn():
    import jax
    import jax.numpy as jnp

    w = jax.random.normal(jax.random.PRNGKey(0), (FEATURES, CLASSES)) * 0.3

    @jax.jit
    def fn(x):
        logits = x @ w
        return {
            "probabilities": jax.nn.softmax(logits, axis=-1),
            "class": jnp.argmax(logits, axis=-1),
        }

    return fn


@pytest.fixture(scope="module")
def artifact(tmp_path_factory, serve_fn):
    from tensorflowdistributedlearning_tpu.train import serving as serving_lib

    directory = str(tmp_path_factory.mktemp("artifact") / "art")
    serving_lib.export_serving_artifact(serve_fn, (1, FEATURES), directory)
    return directory


def test_export_ships_no_cache_of_its_own(artifact):
    """The artifact is the module and its manifest. Where compiled ladders
    live is the resolver's business — an exporter that sees other devices
    than the server could not have produced entries the server would hit."""
    from tensorflowdistributedlearning_tpu.train import serving as serving_lib

    assert sorted(os.listdir(artifact)) == sorted(
        [serving_lib.ARTIFACT_NAME, serving_lib.MANIFEST_NAME]
    )
    assert "compile_cache" not in serving_lib.read_manifest(artifact)


_LOAD_SCRIPT = """
import json, sys
sys.path.insert(0, {repo!r})
from tensorflowdistributedlearning_tpu.utils import compile_cache
assert compile_cache.configure({cache_dir!r})
from tensorflowdistributedlearning_tpu.serve.engine import InferenceEngine
eng = InferenceEngine.from_artifact({artifact!r}, buckets=(1, 4))
timings = eng.warmup()
print(json.dumps({{
    "stats": compile_cache.stats(),
    "warmed": sorted(eng.warmed_buckets),
    "timings": {{str(k): v for k, v in timings.items()}},
}}))
"""


def _load_replica(artifact: str, cache_dir: str) -> dict:
    script = _LOAD_SCRIPT.format(
        repo=REPO, cache_dir=cache_dir, artifact=artifact
    )
    out = subprocess.run(
        [sys.executable, "-c", script], env=_env(), capture_output=True,
        text=True, timeout=240,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def two_server_starts(artifact, tmp_path_factory):
    cache_dir = str(tmp_path_factory.mktemp("replica_cache"))
    return [_load_replica(artifact, cache_dir) for _ in range(2)]


def test_first_server_start_compiles_the_ladder(two_server_starts):
    first, _ = two_server_starts
    assert first["warmed"] == [1, 4]
    assert first["stats"]["misses"] >= 2
    assert first["stats"]["hits"] == 0


def test_second_server_start_is_compile_free(two_server_starts):
    _, second = two_server_starts
    # every warmup compile answered from what the first start cached
    assert second["warmed"] == [1, 4]
    assert second["stats"]["hits"] >= 2
    assert second["stats"]["misses"] == 0


def test_engine_load_never_places_a_cache_in_a_temp_dir(
    artifact, monkeypatch
):
    """No cache configured (this CPU-pinned suite): loading and warming an
    artifact must neither configure one nor create a temp dir for one — a
    directory that moves never hits."""
    import tempfile

    from tensorflowdistributedlearning_tpu.serve.engine import InferenceEngine

    def no_temp_dirs(*args, **kwargs):
        raise AssertionError("a temp dir was created on the load path")

    monkeypatch.setattr(tempfile, "mkdtemp", no_temp_dirs)
    before = compile_cache.active_dir()
    eng = InferenceEngine.from_artifact(artifact, buckets=(1, 4))
    eng.warmup()
    assert eng.warmed_buckets == {1, 4}
    assert compile_cache.active_dir() == before


# -- parallel warmup: ordering + accounting ----------------------------------


def test_parallel_warmup_accounting_and_warm_mark(tmp_path, serve_fn):
    from tensorflowdistributedlearning_tpu.obs import Telemetry
    from tensorflowdistributedlearning_tpu.serve.engine import InferenceEngine

    eng = InferenceEngine(serve_fn, (FEATURES,), buckets=(1, 4, 8))
    tel = Telemetry(str(tmp_path), run_info={"kind": "serve"})
    timings = eng.warmup(telemetry=tel)
    assert set(timings) == {1, 4, 8}
    assert eng.warmed and eng.warmed_buckets == {1, 4, 8}
    assert all(t >= 0 for t in timings.values())
    # the warm mark landed strictly after every bucket: steady-state traffic
    # on warmed shapes triggers zero post-warmup recompiles
    x = np.random.default_rng(0).normal(size=(3, FEATURES)).astype("float32")
    eng.infer(x)
    assert tel.detector.post_warmup_count == 0
    tel.close()
    events = obs.read_ledger(str(tmp_path))
    warmup_events = [e for e in events if e.get("event") == "serve_warmup"]
    assert len(warmup_events) == 1
    assert sorted(warmup_events[0]["buckets"]) == ["1", "4", "8"]


def test_deferred_warm_mark_for_multi_engine_load(tmp_path, serve_fn):
    """mark_warm=False (the multi-engine registry path) must leave the
    detector unarmed so a SECOND engine's warmup is not flagged."""
    from tensorflowdistributedlearning_tpu.obs import Telemetry
    from tensorflowdistributedlearning_tpu.serve.engine import InferenceEngine

    tel = Telemetry(str(tmp_path), run_info={"kind": "serve"})
    a = InferenceEngine(serve_fn, (FEATURES,), buckets=(1, 4))
    a.warmup(telemetry=tel, mark_warm=False)
    b = InferenceEngine(lambda x: {"y": x * 3.0}, (FEATURES,), buckets=(2,))
    b.warmup(telemetry=tel, mark_warm=False)
    assert tel.detector.post_warmup_count == 0
    tel.mark_warm()
    tel.close()


# -- replica time_to_ready_s + compile split in report/top -------------------


def test_replica_ttr_surfaces_in_report_and_top(tmp_path):
    from tensorflowdistributedlearning_tpu.obs import fleet as fleet_lib
    from tensorflowdistributedlearning_tpu.obs import top as top_lib

    ledger = obs.RunLedger(str(tmp_path))
    ledger.event("run_header", schema_version=1, kind="serve-fleet")
    ledger.event("replica_spawn", replica=0, port=9001)
    ledger.event("replica_ready", replica=0, port=9001, time_to_ready_s=6.4)
    ledger.event("replica_spawn", replica=1, port=9002)
    ledger.event("replica_ready", replica=1, port=9002, time_to_ready_s=1.6)
    ledger.close()

    report = build_report(str(tmp_path))
    ttr = report["serve_fleet"]["replicas"]["time_to_ready_s"]
    assert ttr["count"] == 2
    assert ttr["mean"] == 4.0
    assert ttr["max"] == 6.4
    assert ttr["last"] == 1.6
    text = render_report(report)
    assert "replica time-to-ready" in text

    led = fleet_lib.discover_ledgers(str(tmp_path))[0]
    row = top_lib._process_status(led, now=led.events[-1]["t"] + 1)
    assert row["last_replica_ready"]["time_to_ready_s"] == 1.6
    assert row["last_replica_ready"]["replica"] == 1


def test_cache_served_compiles_split_from_recompiles(tmp_path):
    """The satellite bugfix: a post-warmup compile the persistent cache
    answered is a LOAD — it must not trip the recompile alarm, but it must
    stay visible."""
    ledger = obs.RunLedger(str(tmp_path))
    ledger.event("run_header", schema_version=1, task="classification")
    ledger.event(
        "compile", duration_s=0.002, phase="train", post_warmup=True,
        cache_hit=True, saved_s=0.5,
    )
    ledger.event(
        "compile", duration_s=1.25, phase="train", post_warmup=True,
        cache_hit=False,
    )
    ledger.close()
    report = build_report(str(tmp_path))
    rc = report["recompiles"]
    assert rc["post_warmup_count"] == 1  # the REAL rebuild only
    assert rc["cache_served_post_warmup"] == 1
    assert rc["post_warmup_s"] == 1.25
    # no run_end totals here: the section falls back to ledgered verdicts
    cc = report["compile_cache"]
    assert cc == {"hits": 1, "misses": 1, "hit_ratio": 0.5, "saved_s": 0.5}
    text = render_report(report)
    assert "1 POST-WARMUP RECOMPILE(S)" in text
    assert "served from the persistent cache" in text
