"""Compile-only checks of the three RSS kernel families for a TPU v5e chip.

Each test compiles one kernel dispatcher (limb decomposition, padding and
the Pallas launch) with ``interpret=False`` at a launch shape of secure
CifarNet2 or CifarNet6 served at batch 32 — the tuples
``cost_model.model_cost(model, (32, 32, 32, 3)).kernel_requests()`` lists —
for a described, not attached, v5e chip.  The compiled program must hold the Mosaic kernel
(``tpu_custom_call``) and fit one chip's HBM.  One test compiles the whole
served CifarNet2 inline runner and counts its launches against that list;
another lowers the CifarNet6 one and counts its kernel calls.  Nothing
runs, so these say nothing about results or times.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.bin_rss_matmul import (PublicGroupedLimbs,
                                          PublicWeightLimbs,
                                          bin_grouped_matmul_parts,
                                          bin_rss_matmul_parts)
from repro.kernels.rss_matmul import WeightLimbs, rss_matmul_parts

V5E_HBM_BYTES = 16 * 10**9
KERNEL_CALL = 'custom_call_target="tpu_custom_call"'
S = 3          # party slots of the local backend
L_PUBLIC = 2   # weight limbs of CifarNet2's public fixed-point weights


def _tile(n):
    return -(-n // 128) * 128


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 host, with the persistent
    compilation cache off: a compile for a described chip cannot be read
    back from it."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # libtpu logs nowhere
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()


def _compile_and_check(fn, args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert KERNEL_CALL in compiled.as_text()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, total


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# every distinct launch shape (9 launches with shared weights, 18 with
# public ones)
# (M, K, N): the pointwise convolutions of the three blocks, classifier head
DENSE = [(32768, 16, 16), (8192, 16, 32), (8192, 32, 32), (2048, 32, 48),
         (2048, 48, 48), (32, 768, 10)]
# CifarNet6 at batch 32 (16 launches either way): the thirteen 3x3
# convolutions' im2col products (K = 9 Cin), the hidden FCs and the head
DENSE_6 = [(32768, 27, 64), (32768, 576, 64), (8192, 576, 128),
           (8192, 1152, 128), (2048, 1152, 256), (2048, 2304, 256),
           (512, 2304, 512), (512, 4608, 512), (128, 4608, 512),
           (32, 512, 512), (32, 512, 10)]
# (M, C): the depthwise convolutions (K = 3x3 taps, N = 1); with shared
# weights they launch no kernel (direct tap products), with public ones
# the grouped public-limb kernel
GROUPED = [(32768, 3), (32768, 16), (8192, 16), (8192, 32), (2048, 32),
           (2048, 48)]


@pytest.mark.parametrize("m,k,n", DENSE + DENSE_6)
def test_rss_matmul_compiles(one_chip, m, k, n):
    u32 = [_spec((S, k, n), jnp.uint32, one_chip)] * 2
    i8 = [_spec((S, 4, _tile(k), _tile(n)), jnp.int8, one_chip)] * 2
    _compile_and_check(
        lambda x, *w: rss_matmul_parts(x, WeightLimbs(*w), interpret=False),
        [_spec((S, m, k), jnp.uint32, one_chip), *u32, *i8])


@pytest.mark.parametrize("m,k,n", DENSE + DENSE_6)
def test_bin_rss_matmul_compiles(one_chip, m, k, n):
    _compile_and_check(
        lambda x, w, wl: bin_rss_matmul_parts(
            x, PublicWeightLimbs(w, wl, L_PUBLIC), interpret=False),
        [_spec((S, m, k), jnp.uint32, one_chip),
         _spec((k, n), jnp.uint32, one_chip),
         _spec((L_PUBLIC, _tile(k), _tile(n)), jnp.int8, one_chip)])


@pytest.mark.parametrize("m,c", GROUPED)
def test_bin_grouped_matmul_compiles(one_chip, m, c):
    k, n = 9, 1
    _compile_and_check(
        lambda x, w, wl: bin_grouped_matmul_parts(
            x, PublicGroupedLimbs(w, wl, L_PUBLIC), interpret=False),
        [_spec((S, c, m, k), jnp.uint32, one_chip),
         _spec((c, k, n), jnp.uint32, one_chip),
         _spec((L_PUBLIC, c, k, n), jnp.int8, one_chip)])



def test_cifarnet2_inline_runner_launches(one_chip, monkeypatch):
    """The served CifarNet2 inline runner (shared weights, batch 32, the
    kernel path) holds one Mosaic kernel per launch that
    ``kernel_requests()`` lists, the dense ones only: its depthwise halves
    are direct tap products, with no grouped launch."""
    from repro.core import RING32, cost_model
    from repro.core.randomness import Parties
    from repro.kernels import lowering
    from repro.launch.serve_secure import build, make_runner
    from repro.nn.bnn import INPUT_SHAPES

    # the CPU backend would pick interpret mode; compile the Mosaic kernels
    monkeypatch.setattr(lowering, "default_interpret", lambda: False)
    net, batch = "CifarNet2", 32
    shape = (batch,) + INPUT_SHAPES[net]
    model = build(net, True, "shared")
    reqs = cost_model.model_cost(model, shape).kernel_requests()
    run, _ = make_runner(model, "local", batch)
    keys = Parties.setup(jax.random.PRNGKey(7)).keys
    text = run.lower(_spec(keys.shape, keys.dtype, one_chip),
                     _spec((S,) + shape, RING32.dtype, one_chip)
                     ).compile().as_text()
    assert reqs and {r[0] for r in reqs} == {"rss_matmul"}
    assert text.count(KERNEL_CALL) == len(reqs)
    assert all("%_rss_matmul_call." in ln
               for ln in text.splitlines() if KERNEL_CALL in ln)


def test_cifarnet6_inline_runner_launches(one_chip, monkeypatch):
    """The served CifarNet6 inline runner (shared weights, batch 32, the
    kernel path) calls the dense Mosaic kernel once per launch that
    ``kernel_requests()`` lists: its thirteen 3x3 convolutions and three
    FC layers, 16 (11 distinct shapes, one kernel function each).  The
    runner's program is lowered for the chip, not compiled, with the
    model's arrays as arguments: with its 0.9 GB of weight shares and
    limbs as constants, the compile takes minutes and about 9 GB of host
    memory here."""
    from conftest import with_array_arguments
    from repro.core import RING32, cost_model
    from repro.core.randomness import Parties
    from repro.kernels import lowering
    from repro.launch.serve_secure import build
    from repro.nn.bnn import INPUT_SHAPES

    monkeypatch.setattr(lowering, "default_interpret", lambda: False)
    net, batch = "CifarNet6", 32
    shape = (batch,) + INPUT_SHAPES[net]
    model = build(net, True, "shared")
    reqs = cost_model.model_cost(model, shape).kernel_requests()
    arrays, run = with_array_arguments(model)
    keys = Parties.setup(jax.random.PRNGKey(7)).keys
    text = jax.jit(run).lower(
        _spec(keys.shape, keys.dtype, one_chip),
        _spec((S,) + shape, RING32.dtype, one_chip),
        [_spec(a.shape, a.dtype, one_chip) for a in arrays]).as_text()
    calls = re.findall(r"call @(_rss_matmul_call[\w.]*)\(", text)
    assert len(reqs) == 16 and {r[0] for r in reqs} == {"rss_matmul"}
    assert len(calls) == len(reqs)
    assert text.count("@tpu_custom_call(") == len(set(calls)) == 11


def test_patch_matrix_is_materialized_before_its_limbs(one_chip):
    """CifarNet6's first convolution (3 channels, K = 27): the compiled
    product holds the patch matrix as a buffer of its own, so the
    taps' concatenation is not fused into the kernel operand's limb
    decomposition, the fusion the TPU compiler got wrong."""
    from repro.core import RING32
    from repro.core.linear import _im2col_rss
    from repro.core.rss import RSS
    m, k, n = DENSE_6[0]

    def first_conv(x, *w):
        cols = _im2col_rss(RSS(x, RING32), 3, 3, 1, 1)[0].shares
        return rss_matmul_parts(cols.reshape(S, m, k), WeightLimbs(*w),
                                interpret=False)
    text = jax.jit(first_conv).lower(
        _spec((S, 32, 32, 32, 3), jnp.uint32, one_chip),
        *[_spec((S, k, n), jnp.uint32, one_chip)] * 2,
        *[_spec((S, 4, _tile(k), _tile(n)), jnp.int8, one_chip)] * 2
    ).compile().as_text()
    assert KERNEL_CALL in text
    assert re.search(r"= u32\[3,32,32,32,27\]", text)


def _dense_call(one_chip):
    m, k, n = DENSE[-1]
    return (lambda x, *w: rss_matmul_parts(x, WeightLimbs(*w),
                                           interpret=False),
            [_spec((S, m, k), jnp.uint32, one_chip),
             *[_spec((S, k, n), jnp.uint32, one_chip)] * 2,
             *[_spec((S, 4, _tile(k), _tile(n)), jnp.int8, one_chip)] * 2])


def _grouped_call(one_chip):
    (m, c), k, n = GROUPED[-1], 9, 1
    return (lambda x, w, wl: bin_grouped_matmul_parts(
                x, PublicGroupedLimbs(w, wl, L_PUBLIC), interpret=False),
            [_spec((S, c, m, k), jnp.uint32, one_chip),
             _spec((c, k, n), jnp.uint32, one_chip),
             _spec((L_PUBLIC, c, k, n), jnp.int8, one_chip)])


@pytest.mark.parametrize("kernel,call", [("_rss_matmul_call", _dense_call),
                                         ("_grouped_public_call",
                                          _grouped_call)])
def test_kernel_keeps_its_name_inside_a_ledger_scope(one_chip, kernel, call):
    """Inside the executor's scopes (``comm.scope``) a kernel launch keeps
    the instruction name the trace reduction matches, and carries the
    scope path in its ``op_name``."""
    from repro.core import comm
    fn, args = call(one_chip)

    def scoped(*a):
        with comm.scope("l3"), comm.scope("l3.pwconv"):
            return fn(*a)
    text = jax.jit(scoped).lower(*args).compile().as_text()
    (line,) = [ln for ln in text.splitlines()
               if KERNEL_CALL in ln and " = " in ln]
    assert line.strip().startswith(f"%{kernel}.")
    assert "/l3/l3.pwconv/" in line.split("op_name=", 1)[1]
