"""A chip belongs to one process at a time: a parent that has initialized a
jax backend holds the chip, and the child it spawns to use that chip fails or
hangs. So the processes that SPAWN chip users — the serve-fleet controller,
the flywheel, the elastic coordinator — must get through their whole job
without initializing a backend. Checked in a fresh interpreter each (this
suite's own process initialized its backend long ago)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRELUDE = """
import json, sys
sys.path.insert(0, {repo!r})
from jax._src import xla_bridge

def backend_initialized():
    return xla_bridge.backends_are_initialized()
"""


def _fresh_interpreter(body: str, **fmt) -> dict:
    script = (_PRELUDE + body).format(repo=REPO, **fmt)
    out = subprocess.run(
        [sys.executable, "-c", script],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=240,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


_CONTROLLER_TELEMETRY = """
from tensorflowdistributedlearning_tpu.obs import Telemetry, read_ledger

tel = Telemetry({workdir!r}, controller=True,
                run_info={{"kind": "serve-fleet", "replicas": 1}})
tel.event("replica_spawn", replica=1, port=1234)
tel.close(kind="serve-fleet")
header = [e for e in read_ledger({workdir!r}) if e["event"] == "run_header"][0]
print(json.dumps({{"initialized": backend_initialized(), "header": header}}))
"""


def test_controller_telemetry_initializes_no_backend(tmp_path):
    res = _fresh_interpreter(_CONTROLLER_TELEMETRY, workdir=str(tmp_path))
    assert res["initialized"] is False
    header = res["header"]
    assert header["controller"] is True and header["kind"] == "serve-fleet"
    # no device fingerprint: the replicas' own ledgers record the device
    assert "fingerprint" not in header


_OWNER_TELEMETRY = """
from tensorflowdistributedlearning_tpu.obs import Telemetry, read_ledger

tel = Telemetry({workdir!r}, run_info={{"kind": "serve"}})
tel.close()
header = [e for e in read_ledger({workdir!r}) if e["event"] == "run_header"][0]
print(json.dumps({{"initialized": backend_initialized(), "header": header}}))
"""


def test_chip_owner_telemetry_records_the_device(tmp_path):
    """The other side of the contract: a process that runs on the device
    says which one, in the header of its own ledger."""
    res = _fresh_interpreter(_OWNER_TELEMETRY, workdir=str(tmp_path))
    assert res["initialized"] is True
    fp = res["header"]["fingerprint"]
    assert fp["platform"] == "cpu" and fp["n_devices"] >= 1
    assert "controller" not in res["header"]


_ELASTIC_PLAN = """
import argparse, dataclasses
from tensorflowdistributedlearning_tpu import cli
from tensorflowdistributedlearning_tpu.configs import get_preset
from tensorflowdistributedlearning_tpu.obs import RunLedger
from tensorflowdistributedlearning_tpu.parallel import planner

planned_on = {{}}
real_plan = planner.plan

def spy(*args, **kwargs):
    planned_on.update(dataclasses.asdict(kwargs["topology"]))
    return real_plan(*args, **kwargs)

planner.plan = spy

# what a chip-owning child of the pod wrote: the device it saw
ledger = RunLedger({workdir!r})
ledger.event("run_header", schema_version=1, fingerprint={{
    "platform": "tpu", "device_kind": "TPU v5 lite", "n_devices": 8,
    "process_index": 0, "process_count": 2, "jax_version": "x"}})
ledger.close()

args = argparse.Namespace(
    model_dir={workdir!r}, devices_per_host=None, hbm_budget_gb=None,
    model_parallel=1, pipeline_parallel=1, sequence_parallel=1,
    expert_parallel=1, weight_update_sharding=None,
)
header = cli._elastic_whatif_plan(
    args, get_preset("cifar10_smoke"), 16, world=1, measured_margin_bytes=None
)
print(json.dumps({{
    "initialized": backend_initialized(),
    "topology": planned_on,
    "layout": header["layout"],
}}))
"""


def test_elastic_whatif_plan_reads_the_device_from_a_child(tmp_path):
    """The coordinator's re-plan at a new world size: device kind and the
    per-host device count come from a child's run header, never from asking
    jax in the coordinator."""
    res = _fresh_interpreter(_ELASTIC_PLAN, workdir=str(tmp_path))
    assert res["initialized"] is False
    topo = res["topology"]
    # 8 devices over 2 processes = 4 per host; the new world is one host
    assert topo["n_devices"] == 4 and topo["process_count"] == 1
    assert topo["device_kind"] == "TPU v5 lite"
    assert res["layout"]  # and a plan came out of it


def test_whatif_plan_without_any_child_header_says_so(tmp_path):
    from tensorflowdistributedlearning_tpu import cli
    from tensorflowdistributedlearning_tpu.obs import RunLedger

    RunLedger(str(tmp_path)).close()
    with pytest.raises(RuntimeError, match="no child run header"):
        cli._child_fingerprint(str(tmp_path))


_CLI_ENTRY = """
from tensorflowdistributedlearning_tpu import cli
rc = cli.main(["presets"])
print(json.dumps({{"initialized": backend_initialized(), "rc": rc}}))
"""


def test_cli_entry_resolves_cache_and_platform_without_a_backend():
    """cli.main's own preamble (platform env, compile-cache resolver) runs for
    every command, controllers included — it must touch no backend."""
    res = _fresh_interpreter(_CLI_ENTRY)
    assert res == {"initialized": False, "rc": 0}
