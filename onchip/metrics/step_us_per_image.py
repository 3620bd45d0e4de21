"""Total time of the benchmark's ``step`` spans over the images completed
in the traced window (host clock)."""


def read(run):
    steps = [t1 - t0 for name, t0, t1 in run.spans if name == "step"]
    if not steps or not run.completed:
        return None
    return sum(steps) / run.completed * 1e6
