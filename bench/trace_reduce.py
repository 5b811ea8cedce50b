"""Reduce a JAX profiler trace (``.xplane.pb``) to the per-layer numbers of
a traced window.

What is read:

* the host span ``bench.window``, written by the harness around the traced
  queries, bounds the window; the other host spans named ``bench.*``
  (dispatch, block, stage, ...) label what the host was doing;
* on each TPU device plane, the ``XLA Ops`` line holds one event per
  device operation, named by the HLO instruction's whole text, and the
  ``XLA Modules`` line one event per program execution, named
  ``<program name>(<id>)``.

What comes out (:func:`reduce_trace`), per device and averaged over the
devices that ran anything in the window:

* ``busy_ns``: the length of the union of the device-op intervals inside
  the window; ``window_ns``; idle is the rest;
* ``kernel_ns``: the device time of kernel launches (Pallas kernels, which
  XLA lowers to ``tpu_custom_call``), with their count, and both split by
  kernel name (the instruction's name without its number);
* ``module_ns``: per program name, the busy time of the ops inside that
  program's executions, and ``module_kernel_ns`` the kernels' part of it;
* ``top_ops``: device ops by total time; ``gaps``: the idle stretches in
  the window, each labelled with the innermost ``bench.*`` host span open
  at its middle.
"""
import bisect
import re

WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KERNEL_MARK = "tpu_custom_call"


def is_kernel(name):
    """A Pallas kernel launch: XLA's custom call into a Mosaic kernel."""
    return KERNEL_MARK in name


def op_name(name):
    """The HLO instruction's name (``_rss_matmul_call.9``) from the event
    name, which on the TPU is the instruction's whole text."""
    m = re.match(r"%?([^\s=]+) = ", name)
    return m.group(1) if m else name


def kernel_name(name):
    """A kernel's name without the instruction's number: the jitted
    function that wraps the ``pallas_call`` (``_rss_matmul_call``)."""
    return re.sub(r"\.\d+$", "", op_name(name))


def load(path):
    """Events of one trace: ``{"host": [...], "devices": {name: {...}}}``,
    times in ns on the trace's common clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    host, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                    op_name(e.name), is_kernel(e.name),
                                    kernel_name(e.name)))
                elif line.name == MODULES_LINE:
                    for e in line.events:
                        mods.append((e.start_ns, e.start_ns + e.duration_ns,
                                     re.sub(r"\(\d+\)$", "", e.name)))
            devices[plane.name] = {"ops": sorted(ops), "modules": sorted(mods)}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name))
    return {"host": sorted(host), "devices": devices}


def union(intervals):
    """Merged, sorted, non-overlapping intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _length(merged):
    return sum(b - a for a, b in merged)


def label_at(host, t):
    """The innermost host span open at ``t`` (the one that began last)."""
    best = None
    for a, b, name in host:
        if a <= t <= b and name != WINDOW_SPAN:
            if best is None or a >= best[0]:
                best = (a, name)
    return best[1] if best else "host: outside bench spans"


def reduce_trace(trace, n_top=10):
    windows = [(a, b) for a, b, n in trace["host"] if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    lo, hi = windows[0]
    per_dev = []
    for name, dev in sorted(trace["devices"].items()):
        ops = [(max(a, lo), min(b, hi), n, k, kn) for a, b, n, k, kn
               in dev["ops"] if b > lo and a < hi]
        if not ops:
            continue
        busy = union((a, b) for a, b, *_ in ops)
        kern = [(a, b, kn) for a, b, _, k, kn in ops if k]
        by_kernel, by_kernel_count = {}, {}
        for a, b, kn in kern:
            by_kernel[kn] = by_kernel.get(kn, 0) + (b - a)
            by_kernel_count[kn] = by_kernel_count.get(kn, 0) + 1
        # ops by program execution (ops lie inside their module's span)
        mods = [m for m in dev["modules"] if m[1] > lo and m[0] < hi]
        starts = [m[0] for m in mods]
        mod_count = {}
        for m in mods:
            mod_count[m[2]] = mod_count.get(m[2], 0) + 1
        mod_ops, mod_kern = {}, {}
        for a, b, _, k, _ in ops:
            i = bisect.bisect_right(starts, a) - 1
            mname = mods[i][2] if i >= 0 and a < mods[i][1] else "(none)"
            mod_ops.setdefault(mname, []).append((a, b))
            if k:
                mod_kern[mname] = mod_kern.get(mname, 0) + (b - a)
        top = {}
        for a, b, n, _, _ in ops:
            top[n] = top.get(n, 0) + (b - a)
        gaps, prev = [], lo
        for a, b in busy + [[hi, hi]]:
            if a > prev:
                gaps.append((a - prev, prev, a))
            prev = max(prev, b)
        per_dev.append({
            "device": name, "window_ns": hi - lo, "busy_ns": _length(busy),
            "kernel_ns": sum(b - a for a, b, _ in kern),
            "kernel_count": len(kern), "by_kernel": by_kernel,
            "by_kernel_count": by_kernel_count,
            "module_ns": {m: _length(union(v)) for m, v in mod_ops.items()},
            "module_kernel_ns": mod_kern, "module_count": mod_count,
            "top_ops": sorted(top.items(), key=lambda kv: -kv[1])[:n_top],
            "gaps": [(label_at(trace["host"], (a + b) / 2), d)
                     for d, a, b in sorted(gaps, reverse=True)[:n_top]],
        })
    if not per_dev:
        return None
    n = len(per_dev)
    mean = lambda key: sum(d[key] for d in per_dev) / n
    merged = lambda key: _merge([d[key] for d in per_dev], n)
    return {
        "devices": n, "window_ns": hi - lo, "busy_ns": mean("busy_ns"),
        "kernel_ns": mean("kernel_ns"),
        "kernel_count": sum(d["kernel_count"] for d in per_dev) / n,
        "by_kernel": merged("by_kernel"),
        "by_kernel_count": merged("by_kernel_count"),
        "module_ns": merged("module_ns"),
        "module_kernel_ns": merged("module_kernel_ns"),
        "module_count": merged("module_count"),
        "top_ops": sorted(merged_pairs(per_dev, "top_ops", n).items(),
                          key=lambda kv: -kv[1])[:n_top],
        "gaps": sorted((g for d in per_dev for g in d["gaps"]),
                       key=lambda g: -g[1])[:n_top],
    }


def _merge(dicts, n):
    out = {}
    for d in dicts:
        for k, v in d.items():
            out[k] = out.get(k, 0) + v / n
    return out


def merged_pairs(per_dev, key, n):
    return _merge([dict(d[key]) for d in per_dev], n)
