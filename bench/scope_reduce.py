"""Reduce a profiler trace by the program's own names: the
``jax.named_scope`` of every ledger tag on the device ops, and the
program's ``cbnn.*`` host spans beside the harness's ``bench.*``.

It extends ``trace_reduce.py`` and returns everything that reduction
returns (:func:`reduce_trace` takes and gives the same shapes), with the
host spans ``cbnn.*`` among the labels (``label_at`` takes the innermost
of either), and adds, per program name:

* ``tag_ns``: the busy time of the non-kernel ops inside each ledger tag's
  innermost scope (``l2.dwconv``; ``l2`` for ops of the head outside its
  protocol calls; ``(none)`` for ops outside every scope);
* ``head_ns``: the busy time of each ledger head's ops (``l3``,
  ``sign4``, ``mp5``, ``output``), kernels included;
* ``class_ns``: the busy time of the non-kernel ops by protocol class
  (:data:`CLASSES`), each the union of its ops' intervals;

and over the window:

* ``idle_by_label``: all of the device's idle time, summed by the label
  of each gap (the innermost named host span open at its middle);
* ``idle_outside_ns``: the idle time while the host was outside every
  named span; ``stage_idle_ns``: the idle time while the host was inside
  a ``cbnn.tape_take`` span (the staging of a tape slice);
* ``first_op_ns``: from the window's start to its first device op;
* ``span_count``: the named host spans that begin in the window, by name;
  ``host_outside_ns``: the host's time outside every named span, by the
  spans before and after it.

An op's scope path is the HLO ``op_name`` of its instruction, which the
TPU trace keeps in the op event's metadata (:func:`tf_ops`).
"""
import re
from pathlib import Path

import trace_reduce as tr

PROGRAM_PREFIX = "cbnn."
STAGE_SPAN = "cbnn.tape_take"
NO_SCOPE = "(none)"
HEAD = re.compile(r"^(?:(l|sign|relu|aff|mp)\d+|(output))$")
# protocol class of each ledger head's prefix
CLASSES = {"l": "linear", "sign": "sign", "relu": "sign", "mp": "maxpool",
           "aff": "affine", "output": "output"}
TF_OP = "tf_op"


def _fields(buf, lo=0, hi=None):
    """The (field number, value) pairs of one protocol-buffer message in
    ``buf[lo:hi]``: an int for varints, a (start, end) span of ``buf`` for
    length-delimited fields; fixed-width fields are skipped."""
    hi = len(buf) if hi is None else hi
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif wire == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protocol-buffer wire type {wire} at {i}")


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def tf_ops(path):
    """Per TPU device plane, the HLO ``op_name`` of each op event's
    instruction, keyed by the event's name: ``{plane: {name: op_name}}``.

    The TPU trace keeps it in the ``tf_op`` stat of the event's metadata
    (``jit(run)/sign4/sign4.msb/xor:``), which ``ProfileData`` does not
    expose, so the ``XSpace`` file is read here: planes (field 1), and in
    each its name (2), event metadata (4: id -> {name 2, stats 5}) and
    stat metadata (5: id -> {name 2}); a stat holds its metadata id (1)
    and a string (5) or a reference to a stat metadata's name (7)."""
    buf = memoryview(Path(path).read_bytes())
    text = lambda span: bytes(buf[span[0]:span[1]]).decode()
    out = {}
    for field, plane in _fields(buf):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(buf, *plane):
            if f == 2:
                name = text(v)
            elif f in (4, 5):
                entry = dict(_fields(buf, *v))
                if 2 not in entry:
                    continue
                if f == 4:
                    events.append(entry[2])
                else:
                    stat_names[entry[1]] = text(dict(_fields(
                        buf, *entry[2])).get(2, (0, 0)))
        if not name.startswith("/device:TPU:") or "Core" in name:
            continue
        tf_id = next((k for k, n in stat_names.items() if n == TF_OP), None)
        ops = out.setdefault(name, {})
        for span in events:
            ev_name, op = "", ""
            for f, v in _fields(buf, *span):
                if f == 2:
                    ev_name = text(v)
                elif f == 5 and tf_id is not None:
                    stat = dict(_fields(buf, *v))
                    if stat.get(1) == tf_id:
                        op = text(stat[5]) if 5 in stat else \
                            stat_names.get(stat.get(7), "")
            if op:
                ops.setdefault(ev_name, op)
    return out


def tag_of(scope):
    """The innermost ledger scope of a scope path: the protocol call's tag
    inside its head (``l2.dwconv``), else the head (``l2``, ``output``),
    else :data:`NO_SCOPE`."""
    head = NO_SCOPE
    for part in scope.split("/"):
        part = part.rstrip(":")
        if head == NO_SCOPE and HEAD.match(part):
            head = part
        elif head != NO_SCOPE and part.startswith(head + "."):
            return part
    return head


def head_of(tag):
    return tag.split(".", 1)[0]


def class_of(head):
    m = HEAD.match(head)
    if not m:
        return NO_SCOPE
    return CLASSES[m.group(1) or m.group(2)]


def load(path):
    """The events of ``trace_reduce.load``, with the ``cbnn.*`` host spans
    among ``host`` and, per device, ``tags``: the innermost ledger scope
    of each op of ``ops`` (:func:`tag_of`), in the same order."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    scopes = tf_ops(path)
    host, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            ops, mods, scope = [], [], scopes.get(plane.name, {})
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    for e in line.events:
                        ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                    tr.op_name(e.name), tr.is_kernel(e.name),
                                    tr.kernel_name(e.name),
                                    tag_of(scope.get(e.name, ""))))
                elif line.name == tr.MODULES_LINE:
                    for e in line.events:
                        mods.append((e.start_ns, e.start_ns + e.duration_ns,
                                     re.sub(r"\(\d+\)$", "", e.name)))
            ops.sort()
            devices[plane.name] = {"ops": [o[:5] for o in ops],
                                   "tags": [o[5] for o in ops],
                                   "modules": sorted(mods)}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith((tr.HOST_PREFIX, PROGRAM_PREFIX)):
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name))
    return {"host": sorted(host), "devices": devices}


def _intersect(a, b):
    """Length of the intersection of two merged interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _scoped(trace, lo, hi):
    """Per device: tag, head and class busy time by program, and the idle
    time by label and inside or outside host spans, over [lo, hi]."""
    def spans(keep):
        return tr.union((max(a, lo), min(b, hi)) for a, b, n in trace["host"]
                        if keep(n) and n != tr.WINDOW_SPAN
                        and b > lo and a < hi)
    stage, named = spans(lambda n: n == STAGE_SPAN), spans(lambda n: True)
    outside = [[a, b] for a, b in zip([lo] + [b for _, b in named],
                                      [a for a, _ in named] + [hi]) if b > a]
    out = []
    for name, dev in sorted(trace["devices"].items()):
        keep = [(max(o[0], lo), min(o[1], hi), o[3], t)
                for o, t in zip(dev["ops"], dev["tags"])
                if o[1] > lo and o[0] < hi]
        if not keep:
            continue
        mods = [m for m in dev["modules"] if m[1] > lo and m[0] < hi]
        starts = [m[0] for m in mods]
        tags, heads, classes = {}, {}, {}
        for a, b, k, t in keep:
            i = tr.bisect.bisect_right(starts, a) - 1
            mname = mods[i][2] if i >= 0 and a < mods[i][1] else "(none)"
            h = head_of(t)
            heads.setdefault(mname, {}).setdefault(h, []).append((a, b))
            if not k:
                tags.setdefault(mname, {}).setdefault(t, []).append((a, b))
                classes.setdefault(mname, {}).setdefault(
                    class_of(h), []).append((a, b))
        busy = tr.union((a, b) for a, b, _, _ in keep)
        idle, by_label, prev = [], {}, lo
        for a, b in busy + [[hi, hi]]:
            if a > prev:
                idle.append([prev, a])
                label = tr.label_at(trace["host"], (prev + a) / 2)
                by_label[label] = by_label.get(label, 0) + (a - prev)
            prev = max(prev, b)
        length = lambda d: {k: tr._length(tr.union(v)) for k, v in d.items()}
        out.append({
            "tag_ns": {m: length(d) for m, d in tags.items()},
            "head_ns": {m: length(d) for m, d in heads.items()},
            "class_ns": {m: length(d) for m, d in classes.items()},
            "idle_by_label": by_label,
            "idle_outside_ns": _intersect(idle, outside),
            "stage_idle_ns": _intersect(idle, stage),
            "first_op_ns": keep[0][0] - lo})
    return out


def span_count(host, lo, hi):
    """Named host spans that begin inside the window, by name."""
    out = {}
    for a, _, name in host:
        if lo <= a <= hi and name != tr.WINDOW_SPAN:
            out[name] = out.get(name, 0) + 1
    return out


def host_outside(host, lo, hi):
    """Host time in the window outside every named span, by the spans
    that bracket it: ``{"<span that ended> > <span that began>": ns}``."""
    spans = sorted((max(a, lo), min(b, hi), n) for a, b, n in host
                   if n != tr.WINDOW_SPAN and b > lo and a < hi)
    out, prev, prev_name = {}, lo, "(window start)"
    for a, b, name in spans + [(hi, hi, "(window end)")]:
        if a > prev:
            key = f"{prev_name} > {name}"
            out[key] = out.get(key, 0) + (a - prev)
        if b >= prev:
            prev, prev_name = b, name
    return out


def reduce_trace(trace, n_top=10):
    """``trace_reduce.reduce_trace`` of ``trace``, plus the keys of the
    module docstring, averaged over the devices that ran anything."""
    red = tr.reduce_trace(trace, n_top)
    if red is None:
        return None
    lo, hi = next((a, b) for a, b, n in trace["host"] if n == tr.WINDOW_SPAN)
    per_dev = _scoped(trace, lo, hi)
    n = len(per_dev)

    def nested(key):
        out = {}
        for d in per_dev:
            for m, inner in d[key].items():
                slot = out.setdefault(m, {})
                for k, v in inner.items():
                    slot[k] = slot.get(k, 0) + v / n
        return out
    mean = lambda key: sum(d[key] for d in per_dev) / n
    red.update({key: nested(key) for key in ("tag_ns", "head_ns",
                                             "class_ns")})
    red.update({key: mean(key) for key in ("idle_outside_ns",
                                           "stage_idle_ns", "first_op_ns")})
    red.update({
        "idle_by_label": tr._merge([d["idle_by_label"] for d in per_dev], n),
        "span_count": span_count(trace["host"], lo, hi),
        "host_outside_ns": host_outside(trace["host"], lo, hi),
    })
    return red
