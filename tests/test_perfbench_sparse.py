"""Tier-1 sees the sparse decoder cell's own CPU tests (``perfbench/tests/
test_sparse_cell.py``): collected here as this module's tests, so each counts
and each failure names itself.

Those tests were written when the cell had eight per-layer metrics and its
windows no ``sparse_select_columns`` / ``sparse_tie_blocks``: here the cell's
ninth metric joins the names they hold ``BENCHMARK.json`` to and the recorded
run gets the two fields, as the program writes them since, until a benchmark
PR brings that file up to date. The ninth metric's own tests are below."""

import pytest

from perfbench import harness, lm_sparse_trace
from perfbench.metrics import sparse_select_counted_share_pct
from perfbench.tests import test_sparse_cell
from perfbench.tests.test_sparse_cell import *  # noqa: F401,F403

COUNTED = "sparse_select_counted_share_pct"
T, LAYERS = 16384, 4

test_sparse_cell.NEW_METRICS = test_sparse_cell.NEW_METRICS + [COUNTED]
_recorded_run = test_sparse_cell._recorded_run


def _recorded_run_with_select_fields():
    run = _recorded_run()
    for window in run.windows:
        window.update(sparse_select_columns=int(0.45 * LAYERS * T * T), sparse_tie_blocks=27.0)
    return run


test_sparse_cell._recorded_run = _recorded_run_with_select_fields


def test_counted_share_is_the_windows_columns_over_the_layers_grids():
    run = _recorded_run_with_select_fields()
    run.windows = run.windows + [dict(run.windows[0], sparse_select_columns=int(0.41 * LAYERS * T * T))]
    assert sparse_select_counted_share_pct.read(run) == pytest.approx(43.0)
    out = harness.read_metrics(run, run.cell.per_layer)
    assert out[COUNTED] == {"value": pytest.approx(43.0), "unit": "%"}
    # the parent's windows hold no such field: no metric, no error
    assert sparse_select_counted_share_pct.read(_recorded_run()) is None
    assert COUNTED not in harness.read_metrics(_recorded_run(), run.cell.per_layer)
    # nor does a cell without sparse layers give one
    other = _recorded_run_with_select_fields()
    other.cell = harness.load_cell("mellum2_share4_train_8k")
    assert sparse_select_counted_share_pct.read(other) is None


# one line of the chip's trace of the Keye cell (PR 31, seed 3600000011): the
# selection's kernel as an ``XLA Ops`` event names it
SELECT_OP = (
    '%sparse_select.5 = (s32[16384,1]{1,0:T(8,128)S(1)}, s32[16384,1]{1,0:T(8,128)S(1)}) '
    'custom-call(s32[128]{0:T(128)S(1)} %copy-done.796, f32[16384,16384]{1,0:T(8,128)} '
    '%sparse_indexer_scores.9), custom_call_target="tpu_custom_call", '
    'operand_layout_constraints={s32[128]{0}, f32[16384,16384]{1,0}}, '
    'frontend_attributes={kernel_metadata={}}')


def test_the_selection_kernel_is_filed_under_select():
    """The kernel takes the ``[T, T]`` scores and the row blocks' first chunks,
    writes two integers a query and no float ``[T, T]``, and moves nothing of
    a head's width: ``sparse_select_device_ms_per_step`` reads it."""
    z = lm_sparse_trace.sizes(harness.load_cell(test_sparse_cell.tiny_sparse.WORKLOAD))
    assert lm_sparse_trace.part_of(SELECT_OP, z) == "select"
