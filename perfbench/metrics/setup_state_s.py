"""Seconds of set-up between the data set and the loop: the plan, and fold
0's ``init_state`` (with the fold's data selection), ``restore`` (with the
wait for the first value off the device) and ``build_step`` (step factory,
input program, summary writers, batch iterator and prefetcher)."""
from perfbench.metrics.setup_load_dataset_s import phase_seconds


def read(run):
    return phase_seconds(run, ("plan", "init_state", "restore", "build_step"))
