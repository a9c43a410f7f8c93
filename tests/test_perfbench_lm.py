"""Tier-1 sees the decoder cell's own CPU tests (``perfbench/tests/
test_lm_cell.py``): collected here as this module's tests, so each counts and
each failure names itself.

Two of those tests were written when the cell had eight per-layer metrics and
its windows no ``moe_buffer_rows`` / ``moe_buffer_fill``: the recorded run
gets the two fields here, as the program writes them since, and the count of
the cell's metrics is held here as ``BENCHMARK.json`` has it now, until a
benchmark PR brings that file up to date. Since PR 34 the cell also lists six
``scope_*`` metrics, which read the step program's ``program_scopes`` record:
the recorded run's ledger gets a made-up one (``tests/test_perfbench_scope.py``
has the readers' own tests)."""

import json
import os

import pytest

from perfbench import harness
from perfbench.tests import test_lm_cell
from perfbench.tests.test_lm_cell import *  # noqa: F401,F403
from tests.conftest import make_scope_record

_recorded_run = test_lm_cell._recorded_run


def _recorded_run_with_buffer_fields(tmp_path):
    run = _recorded_run(tmp_path)
    for window in run.windows:
        window.update(moe_buffer_rows=[40960.0] * 4, moe_buffer_fill=[0.8, 0.79, 0.81, 0.8])
    run.ledger = run.ledger + [make_scope_record(run.trace)]
    return run


test_lm_cell._recorded_run = _recorded_run_with_buffer_fields


def test_benchmark_names_the_cells_files():  # noqa: F811 - the cell's ninth metric
    with open(os.path.join(test_lm_cell.tiny_lm.REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [test_lm_cell.tiny_lm.WORKLOAD]]
    assert [m["name"] for m in mine][8:] == ["moe_buffer_fill_pct"]
    for metric in mine:
        path = os.path.join(test_lm_cell.HERE, "..", "metrics", metric["name"].replace(".", "_") + ".py")
        assert os.path.exists(path), metric["name"]
        assert metric["moves"] == "train_images_per_s"
    cell = harness.load_cell(test_lm_cell.tiny_lm.WORKLOAD)
    assert cell.chips == 1 and cell.traffic["entry"] == "lm_fit" and set(cell.limits)


def test_buffer_fill_is_the_least_filled_layer(tmp_path):
    run = _recorded_run_with_buffer_fields(tmp_path)
    out = harness.read_metrics(run, run.cell.per_layer)
    assert out["moe_buffer_fill_pct"] == {"value": pytest.approx(79.0), "unit": "%"}
    # on the parent the windows hold no such field: no metric, no error
    run.windows = [{k: v for k, v in w.items() if not k.startswith("moe_buffer")}
                   for w in run.windows]
    assert "moe_buffer_fill_pct" not in harness.read_metrics(run, run.cell.per_layer)
