"""Tier-1 sees the benchmark's own CPU tests, starting with the ones that
need no jax: the readers of the program's host timeline, on a paper ledger
(``perfbench/tests/test_span_metrics.py``). They are collected here as this
module's tests, so each counts and each failure names itself."""

from perfbench.tests.test_span_metrics import *  # noqa: F401,F403
