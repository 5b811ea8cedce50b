"""Plain reference for the binarized CNN classifiers: the forward pass of a
sequential layer list in straightforward ``jax.numpy``.

It imports nothing of the program under test.  The layer list comes from
the configuration file (``layers``), the weights from the benchmark's own
generator (``bench/families/bnn_classifier.py``), keyed as the program keys
them (``l{i}_w``, ``l{i}_dw``, ``l{i}_pw``, ``l{i}_b``, ``l{i}_g``, ...).

Layers, in NHWC / HWIO layout:

* ``conv``: dense convolution, then bias;
* ``sepconv``: depthwise convolution (multiplier 1, no bias), then a 1x1
  pointwise convolution to ``out`` channels, then bias;
* ``fc``: matrix product, then bias;
* ``bn``: ``(x - mu) * rsqrt(var + 1e-5) * g + beta`` (inference statistics);
* ``act``: Sign (``x >= 0`` gives +1, else -1) or ReLU;
* ``maxpool``: 2x2, stride 2;
* ``flatten``: row-major over (H, W, C).

``dtype="float32"`` is the reference: every product at
``Precision.HIGHEST``, so the TPU does not drop to bfloat16 passes.
``dtype="bfloat16"`` is the control: every tensor is held in bfloat16,
the nearest float type below float32 (products of bfloat16 operands
accumulate exactly, as on the MXU, and each layer's result is rounded back
to bfloat16).  The rounding is ``lax.reduce_precision``, which XLA keeps:
a float32 -> bfloat16 -> float32 round trip of ``astype`` may be dropped
under ``jit`` as excess precision, and the control would read as the
reference.
"""
import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
BN_EPS = 1e-5


MANTISSA_BITS = {"float32": 23, "bfloat16": 7}


def _conv(x, w, stride, pad, groups):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=HIGHEST)


def forward(params, x, layers, dtype="float32"):
    """Logits of ``x`` (B, H, W, C) under ``layers``, as float32."""
    bits = MANTISSA_BITS[dtype]

    def r(v):
        return v if bits == 23 else jax.lax.reduce_precision(v, 8, bits)

    p = {k: r(v.astype(jnp.float32)) for k, v in params.items()}
    x = r(x.astype(jnp.float32))
    for i, l in enumerate(layers):
        kind = l["kind"]
        if kind == "conv":
            x = r(_conv(x, p[f"l{i}_w"], l.get("stride", 1), l.get("pad", 0),
                        1))
            x = r(x + p[f"l{i}_b"])
        elif kind == "sepconv":
            x = r(_conv(x, p[f"l{i}_dw"], l.get("stride", 1),
                        l.get("pad", 0), x.shape[-1]))
            x = r(_conv(x, p[f"l{i}_pw"], 1, 0, 1))
            x = r(x + p[f"l{i}_b"])
        elif kind == "fc":
            x = r(jnp.dot(x, p[f"l{i}_w"], precision=HIGHEST))
            x = r(x + p[f"l{i}_b"])
        elif kind == "bn":
            x = r((x - p[f"l{i}_mu"]) * r(jax.lax.rsqrt(p[f"l{i}_var"]
                                                        + BN_EPS))
                  * p[f"l{i}_g"] + p[f"l{i}_beta"])
        elif kind == "act":
            if l.get("act", "sign") == "sign":
                x = jnp.where(x >= 0, 1.0, -1.0)
            else:
                x = jnp.maximum(x, 0)
        elif kind == "maxpool":
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        elif kind == "flatten":
            x = x.reshape(x.shape[0], -1)
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
    return x
