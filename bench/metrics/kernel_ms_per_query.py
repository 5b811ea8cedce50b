"""Device time of the Pallas kernel launches per query of the traced
window, per device."""


def read(run):
    t = run.trace
    if not t or not run.queries or t["kernel_count"] == 0:
        return None
    return t["kernel_ns"] / run.queries / 1e6
