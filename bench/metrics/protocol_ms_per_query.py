"""Device time of the online program's operations other than kernel
launches (Sign/MSB, maxpool, truncation, reshares, PRF draws, limb
decomposition) per query of the traced window, per device."""


def read(run):
    t, name = run.trace, run.programs.get("online")
    if not t or not run.queries or name not in t["module_ns"]:
        return None
    ns = t["module_ns"][name] - t["module_kernel_ns"].get(name, 0)
    return ns / run.queries / 1e6
