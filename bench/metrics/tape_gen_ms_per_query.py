"""Device time of the tape generator's program per query's worth of
material it generated in the traced window (executions x pool depth)."""


def read(run):
    t, name = run.trace, run.programs.get("generator")
    if not t or name not in t["module_ns"] or not t["module_count"].get(name):
        return None
    slices = t["module_count"][name] * int(run.traffic["pool_depth"])
    return t["module_ns"][name] / slices / 1e6
