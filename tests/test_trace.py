"""obs/trace.py: the request/step-granular trace layer.

Contracts under test, the ones the acceptance criteria name: spans nest and
parent correctly with per-trace sampling; a served request's trace shows
queue_wait→pad→compute child spans linked (``batch_span_id``) to its batch's
compute span, with the trace id echoed as ``x-request-id`` on success AND on
shed/timeout errors; a training run's trace shows step/eval/checkpoint spans;
and the exported Chrome/Perfetto JSON carries every required trace-event
field."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from tensorflowdistributedlearning_tpu import obs
from tensorflowdistributedlearning_tpu.obs import trace as trace_lib
from tensorflowdistributedlearning_tpu.serve import (
    InferenceEngine,
    MicroBatcher,
    ServingServer,
)

FEATURES = 4
CLASSES = 3


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


# -- tracer unit behavior ----------------------------------------------------


def test_null_tracer_is_inert():
    assert not trace_lib.NULL_TRACER.enabled
    with trace_lib.NULL_TRACER.span("anything") as span:
        assert span is None
    assert trace_lib.NULL_TRACER.current() is None


def test_span_nesting_parents_and_children():
    written = []
    tracer = trace_lib.Tracer(emit=written.append, sample_rate=1.0)
    with tracer.span("root", attrs={"k": 1}) as root:
        with tracer.span("child") as child:
            assert tracer.current() is child
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
        with tracer.span("sibling") as sib:
            assert sib.parent_id == root.span_id
    assert tracer.current() is None
    # children collected on the open parent (the batcher relies on this)
    assert [c.name for c in root.children] == ["child", "sibling"]
    # written innermost-first, all sampled, ids unique
    assert [w["name"] for w in written] == ["child", "sibling", "root"]
    assert len({w["span_id"] for w in written}) == 3
    assert written[-1].get("parent_id") is None
    assert written[-1]["attrs"] == {"k": 1}
    assert all(w["duration_s"] >= 0 for w in written)


def test_sampling_is_decided_per_trace():
    written = []
    tracer = trace_lib.Tracer(emit=written.append, sample_rate=0.5)
    # an unsampled root drops its whole trace — children included — while
    # ids still exist for propagation
    with tracer.span("root", sampled=False) as root:
        with tracer.span("child") as child:
            assert child.sampled is False
        assert root.span_id
    assert written == []
    with tracer.span("root", sampled=True):
        with tracer.span("child"):
            pass
    assert [w["name"] for w in written] == ["child", "root"]
    # retroactive emits respect the caller's verdict too
    tracer.emit("late", trace_id="t", start_t=0.0, duration_s=1.0, sampled=False)
    assert len(written) == 2
    tracer.emit("late", trace_id="t", start_t=0.0, duration_s=1.0)
    assert written[-1]["name"] == "late"


def test_tracer_rejects_bad_sample_rate():
    with pytest.raises(ValueError, match="sample_rate"):
        trace_lib.Tracer(emit=lambda e: None, sample_rate=1.5)


# -- serve request path ------------------------------------------------------


def _post(url, payload, timeout=10, headers=None):
    req = urllib.request.Request(
        url,
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, dict(resp.headers), json.loads(resp.read())


@pytest.fixture
def traced_server(serve_fn, tmp_path):
    workdir = str(tmp_path / "serve_traced")
    tel = obs.Telemetry(
        workdir, run_info={"kind": "serve"}, trace_sample_rate=1.0
    )
    engine = InferenceEngine(
        serve_fn,
        (FEATURES,),
        buckets=(4,),
        registry=tel.registry,
        tracer=tel.tracer,
    )
    engine.warmup(telemetry=tel)
    batcher = MicroBatcher(engine, max_wait_ms=2, max_queue=16)
    server = ServingServer(
        engine, batcher, port=0, telemetry=tel, window_secs=0
    ).start()
    yield server, workdir
    server.shutdown()


def _trace_events(workdir, server=None):
    deadline = time.monotonic() + 5.0
    while True:
        if server is not None:
            # trace events are buffered (no flush per span); push them to disk
            # before reading a LIVE server's ledger
            server.telemetry.flush()
        events = [
            e for e in obs.read_ledger(workdir) if e.get("event") == "trace"
        ]
        # the handler closes its "request" span AFTER the response has gone
        # out: a client that reads at once can be ahead of it on a busy host
        if (
            server is None
            or any(e["name"] == "request" for e in events)
            or time.monotonic() > deadline
        ):
            return events
        time.sleep(0.01)


def test_request_trace_links_queue_pad_compute_to_batch(traced_server):
    server, workdir = traced_server
    x = np.ones((2, FEATURES), np.float32)  # n=2 < bucket 4: padding happens
    status, headers, body = _post(
        server.url + "/v1/predict", {"instances": x.tolist()}
    )
    assert status == 200 and body["n"] == 2
    rid = headers["x-request-id"]
    assert rid

    spans = _trace_events(workdir, server)
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    # the echoed x-request-id IS the request trace id
    request_spans = [
        e for e in by_name["request"] if e["trace_id"] == rid
    ]
    assert len(request_spans) == 1
    root = request_spans[0]
    assert root.get("parent_id") is None
    assert root["attrs"]["status"] == 200

    # queue→pad→compute children of the request root, in its trace
    members = {
        name: [
            e
            for e in by_name.get(name, [])
            if e["trace_id"] == rid and e.get("parent_id") == root["span_id"]
        ]
        for name in ("queue_wait", "pad", "compute")
    }
    for name, found in members.items():
        assert len(found) == 1, f"missing member span {name}: {spans}"

    # the member pad/compute spans link to the batch trace's compute span
    batch_roots = by_name.get("batch", [])
    assert batch_roots, "batcher wrote no batch span"
    batch = batch_roots[-1]
    batch_compute = [
        e
        for e in by_name["compute"]
        if e["trace_id"] == batch["trace_id"]
        and e.get("parent_id") == batch["span_id"]
    ]
    assert len(batch_compute) == 1
    link = members["compute"][0]["attrs"]
    assert link["batch_span_id"] == batch_compute[0]["span_id"]
    assert link["batch_trace_id"] == batch["trace_id"]
    assert members["compute"][0]["attrs"]["bucket"] == 4


def test_client_supplied_request_id_is_honored(traced_server):
    server, workdir = traced_server
    x = np.ones((1, FEATURES), np.float32)
    status, headers, _ = _post(
        server.url + "/v1/predict",
        {"instances": x.tolist()},
        headers={"x-request-id": "my-req-42"},
    )
    assert status == 200
    assert headers["x-request-id"] == "my-req-42"
    assert any(
        e["name"] == "request" and e["trace_id"] == "my-req-42"
        for e in _trace_events(workdir, server)
    )


def test_error_responses_carry_request_id_and_kind(serve_fn, tmp_path):
    """429 (shed) and 400 (malformed) answers are correlatable: machine-
    readable error.code + the request id in body and header."""
    import time as time_lib

    barrier = threading.Event()

    def slow_fn(x):
        barrier.wait(timeout=10)
        return serve_fn(x)

    engine = InferenceEngine(slow_fn, (FEATURES,), buckets=(1,))
    batcher = MicroBatcher(engine, max_wait_ms=1, max_queue=1)
    server = ServingServer(engine, batcher, port=0, window_secs=0).start()
    try:
        results = []

        def post_one():
            try:
                _post(
                    server.url + "/v1/predict",
                    {"instances": [[0.0] * FEATURES]},
                    timeout=15,
                )
                results.append((200, None, None))
            except urllib.error.HTTPError as err:
                body = json.loads(err.read())
                results.append(
                    (err.code, body["error"], err.headers.get("x-request-id"))
                )

        # one in flight (worker blocked), one queued, the rest shed with 429
        threads = [threading.Thread(target=post_one) for _ in range(4)]
        for t in threads:
            t.start()
            time_lib.sleep(0.05)
        barrier.set()
        for t in threads:
            t.join(timeout=15)
        shed = [r for r in results if r[0] == 429]
        assert shed, f"expected at least one 429, got {results}"
        for _, error, header_rid in shed:
            assert error["code"] == "queue_full"
            assert error["request_id"]
            assert header_rid == error["request_id"]

        # malformed request: same contract on the 400 path
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(server.url + "/v1/predict", {"wrong": []})
        body = json.loads(err.value.read())
        assert err.value.code == 400
        assert body["error"]["code"] == "bad_request"
        assert body["error"]["request_id"]
        assert err.value.headers.get("x-request-id") == body["error"]["request_id"]

        # a POST 404 mints its OWN id — never echoes a previous request's
        # (keep-alive handler instances are reused across requests)
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(server.url + "/v1/nope", {"instances": []})
        body = json.loads(err.value.read())
        assert err.value.code == 404
        assert body["error"]["request_id"]
    finally:
        server.shutdown()


# -- chrome export -----------------------------------------------------------

CHROME_REQUIRED = ("name", "ph", "ts", "pid", "tid")


def _assert_valid_chrome(doc):
    assert "traceEvents" in doc
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert xs, "no complete events in export"
    for e in doc["traceEvents"]:
        for field in CHROME_REQUIRED:
            assert field in e, f"missing {field}: {e}"
    for e in xs:
        assert "dur" in e and e["dur"] >= 0
        assert e["ts"] >= 0
    return xs


def test_chrome_export_from_serve_trace(traced_server, tmp_path):
    server, workdir = traced_server
    x = np.ones((3, FEATURES), np.float32)
    _post(server.url + "/v1/predict", {"instances": x.tolist()})
    _trace_events(workdir, server)  # flushed, the request's own span included
    out = str(tmp_path / "trace.json")
    n = trace_lib.write_chrome_trace(workdir, out)
    with open(out) as f:
        doc = json.load(f)
    xs = _assert_valid_chrome(doc)
    assert len(xs) == n
    names = {e["name"] for e in xs}
    assert {"request", "queue_wait", "compute"} <= names
    # parenting survives the export (in args), and the request's compute
    # child still points at its batch
    by_span = {e["args"]["span_id"]: e for e in xs if "span_id" in e["args"]}
    linked = [e for e in xs if "batch_span_id" in e.get("args", {})]
    assert linked
    for e in linked:
        assert e["args"]["batch_span_id"] in by_span
    # the flow links rendered too (s/f pairs share ids)
    flows = [e for e in doc["traceEvents"] if e.get("ph") in ("s", "f")]
    assert flows and len(flows) % 2 == 0


def test_chrome_export_empty_ledger_is_valid(tmp_path):
    workdir = str(tmp_path / "empty")
    tel = obs.Telemetry(workdir, run_info={})
    tel.close()
    out = str(tmp_path / "trace.json")
    assert trace_lib.write_chrome_trace(workdir, out) == 0
    with open(out) as f:
        assert json.load(f)["traceEvents"] == []


# -- training run ------------------------------------------------------------

TINY = dict(
    num_classes=4,
    input_shape=(16, 16),
    input_channels=3,
    n_blocks=(1, 1, 1),
    width_multiplier=0.125,
    output_stride=None,
)


@pytest.fixture(scope="module")
def traced_fit_workdir(tmp_path_factory):
    """One short synthetic fit() with tracing fully on."""
    from tensorflowdistributedlearning_tpu.config import ModelConfig, TrainConfig
    from tensorflowdistributedlearning_tpu.train.fit import ClassifierTrainer

    workdir = str(tmp_path_factory.mktemp("traced_fit"))
    trainer = ClassifierTrainer(
        workdir,
        None,
        ModelConfig(**TINY),
        TrainConfig(
            train_log_every_steps=2,
            checkpoint_every_steps=4,
            eval_every_steps=4,
            trace_sample_rate=1.0,
        ),
    )
    trainer.fit(batch_size=8, steps=8, eval_every_steps=4)
    return workdir


def test_training_run_traces_step_eval_checkpoint(traced_fit_workdir):
    spans = _trace_events(traced_fit_workdir)
    names = {e["name"] for e in spans}
    assert {"step", "eval", "checkpoint"} <= names, names
    # rate 1.0: every train step traced
    assert sum(1 for e in spans if e["name"] == "step") >= 8


def test_training_trace_exports_and_cli(traced_fit_workdir, tmp_path, capsys):
    from tensorflowdistributedlearning_tpu.cli import main

    out = str(tmp_path / "train_trace.json")
    rc = main(["telemetry-report", traced_fit_workdir, "--export-trace", out])
    assert rc == 0
    verdict = json.loads(capsys.readouterr().out)
    assert verdict["written"] == out and verdict["span_events"] > 0
    with open(out) as f:
        xs = _assert_valid_chrome(json.load(f))
    assert {"step", "eval", "checkpoint"} <= {e["name"] for e in xs}


def test_report_renders_trace_summary(traced_fit_workdir):
    from tensorflowdistributedlearning_tpu.obs.report import report_workdir

    rendered = report_workdir(traced_fit_workdir)
    assert "tracing:" in rendered and "--export-trace" in rendered


def test_cli_parser_accepts_observability_flags():
    from tensorflowdistributedlearning_tpu.cli import build_parser

    args = build_parser().parse_args(
        ["fit", "--preset", "p", "--model-dir", "m",
         "--trace-sample-rate", "0.5", "--nan-guard", "abort"]
    )
    assert args.trace_sample_rate == 0.5 and args.nan_guard == "abort"
    args = build_parser().parse_args(
        ["serve", "--artifact-dir", "d", "--slo-p99-ms", "50",
         "--trace-sample-rate", "0.1"]
    )
    assert args.slo_p99_ms == 50.0 and args.slo_error_budget == 0.01
    # defaults leave the config in charge
    args = build_parser().parse_args(
        ["train", "--model-dir", "m", "--data-dir", "d"]
    )
    assert args.trace_sample_rate is None and args.nan_guard is None
