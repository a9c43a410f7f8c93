"""The longest gap between the completion times of two consecutive train
steps (``step_window.step_done_mono``: the host's clock when its dispatch
tracker's wait on the step returned), over the ledger windows inside the
measured window. Only steps dispatched after the harness's capture stopped
count: its drain and the profiler's stop stall the host inside the window,
and that stall is not the program's. A stall of the program's own — a window
emission or an image summary that holds a step back — shows here and in no
mean."""
from perfbench.metrics.setup_outside_train_s import window_first_step


def read(run):
    done = {}
    for w in run.windows:
        for i, t in enumerate(w.get("step_done_mono", ())):
            done[w["step_done_first"] + i] = t
    after = window_first_step(run) - 1 + run.trace_steps + 2
    gaps = [
        done[step] - done[step - 1]
        for step in done
        if step - 1 > after and step - 1 in done
    ]
    return 1e3 * max(gaps) if gaps else None
