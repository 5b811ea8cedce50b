"""Share of the traced window in which no operation ran on the device,
averaged over the devices that ran any."""


def read(run):
    t = run.trace
    if not t or t["window_ns"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_ns"] / t["window_ns"])
