"""Seconds of ``setup_s`` that lie outside the program's ``train()``:
``setup_s`` less the stretch from the first ``startup_phase``'s entry to the
entry of the ``step`` span of the window's first step, both on the program's
``perf_counter``. That is imports, device init, the harness's weights and
data set and the trainer's constructor before ``train()``, plus, at the
window's opening, the dispatch of that step's input program and the
harness's drain (and profiler start, in a traced run) inside its train-step
call, which a reader cannot tell apart."""
from perfbench import harness


def step_start(run, step):
    """``perf_counter`` at the entry of the ``step`` span of train step
    ``step``, or None where the ledger's windows do not carry it."""
    for e in run.ledger:
        if e.get("event") != "step_window" or "step_start_mono" not in e:
            continue
        first = e["step"] - e["steps"] + 1
        starts = e["step_start_mono"]
        if first <= step <= e["step"] and len(starts) == e["steps"]:
            return starts[step - first]
    return None


def window_first_step(run):
    """The number of the train step whose call opens the measured window."""
    return max(int(run.cell.traffic["warmup_steps"]), harness.FOLLOWED_STEPS) + 1


def read(run):
    entered = next(
        (e["t0_mono"] for e in run.ledger if e.get("event") == "startup_phase"), None
    )
    opened = step_start(run, window_first_step(run))
    if entered is None or opened is None:
        return None
    return run.setup_s - (opened - entered)
