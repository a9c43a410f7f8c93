"""The head gates' mean in per cent (``step_window.attn_gate_mean`` by layer
type, each type weighted by the gates it has: layers x heads), over the ledger
windows inside the measured window: 50 for gates that know nothing, lower
where training has learnt to close heads. None where the program writes no
such field (an ungated model)."""
from perfbench import flops_mixed, lm_mixed_trace


def read(run):
    seen = lm_mixed_trace.counters(run)
    if seen is None or not seen.get("gate_mean"):
        return None
    gates = {kind: sum(flops_mixed.heads_of_kind(run.cell.config, kind))
             for kind in seen["gate_mean"]}
    total = sum(gates.values())
    return 100.0 * sum(seen["gate_mean"][k] * n for k, n in gates.items()) / total if total else None
