"""Tier-1 sees the decoder cell's own CPU tests (``perfbench/tests/
test_lm_cell.py``): collected here as this module's tests, so each counts and
each failure names itself."""

from perfbench.tests.test_lm_cell import *  # noqa: F401,F403
