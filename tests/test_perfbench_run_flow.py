"""Tier-1 sees the one CPU test that drives the benchmark's whole flow — the
observed trainer subclass, the swapped step factory, the probe, the
reference, ``correct`` — through the program's own loop (``train/loop.py``):
``perfbench/tests/test_run_flow.py``, collected here as this module's tests,
so each counts and each failure names itself."""

import os
import sys

# the file imports its neighbour ``tiny`` by its bare name
_NEIGHBOURS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perfbench", "tests"
)
if _NEIGHBOURS not in sys.path:
    sys.path.insert(0, _NEIGHBOURS)

from perfbench.tests.test_run_flow import *  # noqa: E402,F401,F403
