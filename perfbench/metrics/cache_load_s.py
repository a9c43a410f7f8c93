"""Seconds the program's own ``compile`` events took in this run: loads from
the compile cache in a warm run, compiles in a cold one."""


def read(run):
    events = [e for e in run.ledger if e.get("event") == "compile"]
    return sum(e.get("duration_s", 0.0) for e in events) if events else None
