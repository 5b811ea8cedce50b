"""Device time of the online program's operations other than kernel
launches inside the Sign and ReLU scopes (``sign*``, ``relu*``: MSB
extraction and its randomness) per query of the traced window, per
device.
Read from the scope reduction (``bench/scope_reduce.py``); nothing to
read in a trace reduced without scopes."""


def read(run):
    t, name = run.trace, run.programs.get("online")
    if not t or not run.queries or name not in t.get("class_ns", {}):
        return None
    return t["class_ns"][name].get("sign", 0) / run.queries / 1e6
