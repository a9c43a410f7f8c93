"""Programs compiled or loaded from the compile cache inside ``train()``
before the loop ran freely: the ``programs`` of every ``startup_phase`` of
fold 0 and before. What the harness itself loads before ``train()`` (its
weights, the trainer's constructor) is not among them."""
from perfbench.metrics.setup_load_dataset_s import startup_phases


def read(run):
    phases = startup_phases(run)
    return sum(e["programs"] for e in phases) if phases else None
