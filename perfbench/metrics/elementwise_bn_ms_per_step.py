"""Device time of the elementwise / BatchNorm / reduce fusions inside the
step program, per step."""


def read(run):
    if run.trace is None:
        return None
    seconds, calls = run.step_buckets()
    return 1e3 * seconds.get("elementwise_bn", 0.0) / calls if calls else None
