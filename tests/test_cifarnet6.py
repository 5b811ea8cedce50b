"""Secure CifarNet6 (binarized VGG16) against the benchmark's plain
reference, on the CPU.

CifarNet6's layer pattern, every width divided by 8 (convolutions
8/8, 16/16, 32x3, 64x3, 64x3, hidden FC 64/64, FC 10) on a 32x32x3 input,
batch 2, grid weights from the benchmark's own generator
(``bench/families/bnn_classifier.py``): every pre-activation sits 1/256 or
more from the Sign boundary, so the secure logits equal the float32
reference's (``bench/refs/bnn_fp32.py``) exactly, under shared and public
weights, with randomness drawn inline or from a tape.

The dense RSS kernel is checked in interpret mode, against exact integer
products, at reduced M for the launch-shape classes CifarNet6 adds to
CifarNet2's: several N blocks (N = 256, 512), K up to 4608 (36 K steps),
and a K of 27 padded to one tile.
"""
import dataclasses
import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import RING32, preprocessing, share
from repro.core.randomness import Parties
from repro.core.secure_model import compile_secure, secure_infer
from repro.kernels.rss_matmul import (precompute_weight_limbs,
                                      rss_matmul_parts)
from repro.nn import bnn

ROOT = Path(__file__).resolve().parent.parent
NET = "CifarNet6/8"
BATCH = 2
SHAPE = (32, 32, 3)
# the benchmark configuration's weights recipe (bench/configs/cifarnet6.json)
WEIGHTS = {"seed": 0, "grid": 8, "levels": 2, "bias_offset": 0.00390625}


def _load(rel, name):
    spec = importlib.util.spec_from_file_location(name, ROOT / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _narrow(layer):
    return (dataclasses.replace(layer, out=layer.out // 8)
            if layer.out not in (0, 10) else layer)


@pytest.fixture(scope="module", autouse=True)
def _quick_compiles():
    """XLA's optimization passes cost most of these eager programs' time
    and change no integer result."""
    was = jax.config.values["jax_disable_most_optimizations"]
    jax.config.update("jax_disable_most_optimizations", True)
    yield
    jax.config.update("jax_disable_most_optimizations", was)


@functools.lru_cache(maxsize=None)
def _setup():
    """The narrow net registered with the program, its layer list as the
    reference reads it, grid weights, pixels and reference logits."""
    spec = [_narrow(l) for l in bnn.ALL_NETS["CifarNet6"]]
    bnn.ALL_NETS[NET], bnn.INPUT_SHAPES[NET] = spec, SHAPE
    layers = [dataclasses.asdict(l) for l in spec]
    family = _load("bench/families/bnn_classifier.py", "bench_family")
    ref = _load("bench/refs/bnn_fp32.py", "bench_reference")
    params = family.make_params(layers, SHAPE, WEIGHTS)
    x = family.make_images(jax.random.PRNGKey(3), 1, BATCH, SHAPE)[0]
    want = np.asarray(jax.jit(lambda p, v: ref.forward(p, v, layers))(
        params, x))
    return params, x, want


@functools.lru_cache(maxsize=None)
def _model(weights):
    params, _, _ = _setup()
    return compile_secure(params, NET, jax.random.PRNGKey(1), RING32,
                          weights=weights)


@pytest.mark.parametrize("weights", ["shared", "public"])
@pytest.mark.parametrize("offline", ["inline", "tape"])
def test_narrow_cifarnet6_equals_plain_reference(weights, offline):
    _, x, want = _setup()
    model = _model(weights)
    xs = share(x, jax.random.PRNGKey(4), RING32)
    keys = Parties.setup(jax.random.PRNGKey(7)).keys
    if offline == "inline":
        got = secure_infer(model, xs, Parties(keys))
    else:
        spec = preprocessing.trace_material(model, (BATCH,) + SHAPE)
        with jax.disable_jit():     # no whole-plant compile
            tape = preprocessing.generate_tape(spec, keys[None])
        got = preprocessing.make_tape_infer(model, spec)(
            keys, xs.shares, tape.query_slice(0))
    got = np.asarray(got, np.float64)
    assert got.shape == (BATCH, 10)
    assert np.abs(got - want).max() == 0.0


def _exact_parts(x, w):
    """z_i = x_i (w_i + w_{i+1}) + x_{i+1} w_i mod 2^32, in numpy."""
    x, w = x.astype(np.uint64), w.astype(np.uint64)
    xn, wn = np.roll(x, -1, axis=0), np.roll(w, -1, axis=0)
    z = [x[i] @ (w[i] + wn[i]) + xn[i] @ w[i] for i in range(3)]
    return (np.stack(z) & 0xFFFFFFFF).astype(np.uint32)


# (M, K, N): CifarNet6's launches at reduced M — the first convolution's
# K = 27, and the deep ones' several N blocks and long contractions
@pytest.mark.parametrize("m,k,n", [(64, 27, 64), (32, 1152, 256),
                                   (16, 2304, 512), (16, 4608, 512)])
def test_dense_kernel_exact_at_cifarnet6_shapes(m, k, n):
    rng = np.random.default_rng(m + k + n)
    x = rng.integers(0, 2**32, (3, m, k), dtype=np.uint32)
    w = rng.integers(0, 2**32, (3, k, n), dtype=np.uint32)
    got = rss_matmul_parts(jnp.asarray(x),
                           precompute_weight_limbs(jnp.asarray(w)),
                           interpret=True)
    assert np.array_equal(np.asarray(got), _exact_parts(x, w))
