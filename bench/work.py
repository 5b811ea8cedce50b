"""The work a cell asks for, counted from the net's shapes alone.

Nothing here reads the program: the counts follow from the configuration's
layer list, the batch and the ring, so every implementation of the same net
is measured against the same work.

* :func:`plaintext_ops_per_image`: operations of the plain forward pass,
  2 per multiply-accumulate of every conv, depthwise, pointwise and fully
  connected layer.  ``mfu`` divides its rate by the chip's int8 peak.
* :func:`kernel_launches`: the secure matrix products a query runs as
  kernels, one per linear layer part (a separable conv has two), each with
  its logical shape ``(m, k, n)`` and group count.  A depthwise part is a
  launch when its ``m`` reaches ``min_dim``, any other part when ``m``,
  ``k`` and ``n`` all do; the program sends smaller products to plain XLA
  (``roofline_percent`` checks the count against the trace).
* :func:`launch_work`: the least work one launch must do on the chip.
  Every operand is a replicated secret share, uniform over the ring, so it
  is ``ring_bits`` wide.  Each of the three parties needs two ring products
  (``x_i (w_i + w_{i+1}) + x_{i+1} w_i``).  A ring product of two
  ``ring_bits``-wide operands on a ``mult_bits`` multiplier takes
  ``L (L + 1) / 2`` partial products, ``L = ring_bits / mult_bits``: the
  other pairs vanish mod 2^ring_bits.  So the operations are
  ``3 * 2 * L (L + 1) / 2 * 2 m k n`` per group.  The bytes are the three
  share stacks of the layer's input activation, weights and output, each
  read or written once at ``ring_bits``: a patch matrix or a padded tile
  that an implementation builds is its own cost, not the layer's.
* :func:`least_seconds`: the larger of operations over the int8 peak and
  bytes over the memory bandwidth; the roofline share of a launch is that
  time over its measured kernel time.
"""
PARTIES = 3
PRODUCTS_PER_PARTY = 2


def _conv_out(h, w, l):
    k, s, p = l.get("k", 3), l.get("stride", 1), l.get("pad", 0)
    return (h + 2 * p - k) // s + 1, (w + 2 * p - k) // s + 1


def linear_parts(layers, input_shape):
    """Per image: one dict per linear part with its input, weight and
    output element counts and its (m, k, n, groups) per image row."""
    h, w, c = input_shape
    flat = None
    parts = []
    for i, l in enumerate(layers):
        kind = l["kind"]
        if kind == "conv":
            k = l.get("k", 3)
            ho, wo = _conv_out(h, w, l)
            parts.append(dict(layer=i, part="conv", rows=ho * wo,
                              k=k * k * c, n=l["out"], groups=1,
                              in_elems=h * w * c, w_elems=k * k * c * l["out"],
                              out_elems=ho * wo * l["out"]))
            h, w, c = ho, wo, l["out"]
        elif kind == "sepconv":
            k = l.get("k", 3)
            ho, wo = _conv_out(h, w, l)
            parts.append(dict(layer=i, part="depthwise", rows=ho * wo,
                              k=k * k, n=1, groups=c,
                              in_elems=h * w * c, w_elems=k * k * c,
                              out_elems=ho * wo * c))
            parts.append(dict(layer=i, part="pointwise", rows=ho * wo,
                              k=c, n=l["out"], groups=1,
                              in_elems=ho * wo * c, w_elems=c * l["out"],
                              out_elems=ho * wo * l["out"]))
            h, w, c = ho, wo, l["out"]
        elif kind == "fc":
            cin = flat if flat is not None else h * w * c
            parts.append(dict(layer=i, part="fc", rows=1, k=cin, n=l["out"],
                              groups=1, in_elems=cin, w_elems=cin * l["out"],
                              out_elems=l["out"]))
            flat = l["out"]
        elif kind == "maxpool":
            h, w = h // 2, w // 2
        elif kind == "flatten":
            flat = h * w * c
    return parts


def plaintext_ops_per_image(layers, input_shape):
    """2 x multiply-accumulates of one image's plain forward pass."""
    return sum(2 * p["rows"] * p["k"] * p["n"] * p["groups"]
               for p in linear_parts(layers, input_shape))


def kernel_launches(layers, input_shape, batch, min_dim=8):
    """The kernel launches of one query of ``batch`` images, in order."""
    out = []
    for p in linear_parts(layers, input_shape):
        m = p["rows"] * batch
        dims = (m,) if p["part"] == "depthwise" else (m, p["k"], p["n"])
        if min(dims) >= min_dim:
            out.append(dict(p, m=m, batch=batch))
    return out


def launch_work(launch, ring_bits=32, mult_bits=8):
    """(operations, bytes) that one launch must at least do."""
    limbs = -(-ring_bits // mult_bits)
    partial = limbs * (limbs + 1) // 2
    ops = (PARTIES * PRODUCTS_PER_PARTY * partial * 2
           * launch["m"] * launch["k"] * launch["n"] * launch["groups"])
    b = launch["batch"]
    elems = (b * launch["in_elems"] + launch["w_elems"]
             + b * launch["out_elems"])
    return ops, PARTIES * elems * ring_bits // 8


def least_seconds(ops, nbytes, peak):
    """(seconds, bound) for ``peak`` = a row of ``bench/peaks.json``."""
    t_ops = ops / peak["int8_ops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def roofline_percent(run, kernel, parts):
    """Share of the roofline that the launches of ``parts`` reached in a
    traced run, where the trace names their kernel ``kernel``: the least
    time of every such launch of the window over their device time.
    ``None`` unless the trace holds exactly the launches the shapes call
    for (one per party device on a mesh)."""
    t = run.trace
    if not t or not run.queries or not t["by_kernel"].get(kernel):
        return None
    launches = [l for l in kernel_launches(
        run.config["layers"], tuple(run.config["input_shape"]),
        int(run.traffic["batch"])) if l["part"] in parts]
    party_devices = 3 if run.config["layout"] == "mesh" else 1
    seen = t["by_kernel_count"][kernel] * t["devices"]
    if abs(seen - len(launches) * run.queries * party_devices) > 0.5:
        return None
    bits = int(run.config["ring"]["bits"])
    least = sum(least_seconds(*launch_work(l, bits), run.peak)[0]
                for l in launches)
    return 100.0 * least * run.queries \
        / (t["by_kernel"][kernel] / 1e9 * t["devices"])
