"""Share of the traced window in which the device was idle while the host
was inside the program's ``cbnn.tape_take`` span (staging one tape slice),
averaged over the devices that ran any.  Read from the scope reduction
(``bench/scope_reduce.py``); nothing to read in a trace reduced without
it."""


def read(run):
    t = run.trace
    if not t or "stage_idle_ns" not in t or t["window_ns"] <= 0:
        return None
    return 100.0 * t["stage_idle_ns"] / t["window_ns"]
