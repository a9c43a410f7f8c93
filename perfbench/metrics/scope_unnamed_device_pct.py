"""Share of the step program's op time in ops with no registered scope, or
with no entry in the program's ``program_scopes`` record."""
from perfbench import scope_trace


def read(run):
    reduced = scope_trace.by_scope(run)
    if reduced is None:
        return None
    seconds, _ = reduced
    total = sum(seconds.values())
    unnamed = sum(v for (scope, _), v in seconds.items() if scope == scope_trace.UNNAMED)
    return 100.0 * unnamed / total if total else None
