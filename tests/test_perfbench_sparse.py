"""Tier-1 sees the sparse decoder cell's own CPU tests (``perfbench/tests/
test_sparse_cell.py``): collected here as this module's tests, so each counts
and each failure names itself."""

from perfbench.tests.test_sparse_cell import *  # noqa: F401,F403
