"""Seconds from the loop's first ``next(batches)`` to the moment its dispatch
tracker retired step 1: where a start loads (or compiles) the input program
and the train step."""
from perfbench.metrics.setup_load_dataset_s import phase_seconds


def read(run):
    return phase_seconds(run, ("first_step",))
