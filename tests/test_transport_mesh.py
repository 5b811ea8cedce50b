"""MeshTransport backend: bit-identical to LocalTransport, and its ledger
matches the compiled per-party HLO's collective wire bytes.

Both tests run in a subprocess with 8 fake host devices (the fake-device
XLA flag must be set before jax initializes, and the main test session must
keep seeing 1 device — same pattern as test_moe_shardmap)."""
from conftest import run_party_subprocess

EQUIV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax

from repro.core import RING32, Parties, share
from repro.core.linear import set_fused_rounds
from repro.core.secure_model import (compile_secure, secure_infer,
                                     secure_infer_mesh)
from repro.nn import bnn


def run_case(net, shape, batch, use_kernel, fused, mesh, batch_axis=None,
             ulp_tol=0, **compile_kw):
    params = bnn.init_bnn(jax.random.PRNGKey(0), net)
    x = (np.random.default_rng(1).integers(0, 2, (batch,) + shape)
         .astype(np.float32) - 0.5)
    model = compile_secure(params, net, jax.random.PRNGKey(2), RING32,
                           use_kernel_dot=use_kernel, **compile_kw)
    xs = share(x, jax.random.PRNGKey(4), RING32)
    try:
        set_fused_rounds(fused)
        loc = secure_infer(model, xs, Parties.setup(jax.random.PRNGKey(3)))
        msh = secure_infer_mesh(model, xs,
                                Parties.setup(jax.random.PRNGKey(3)),
                                mesh, batch_axis=batch_axis)
    finally:
        set_fused_rounds(True)
    a, b = np.asarray(loc), np.asarray(msh)
    if ulp_tol == 0:
        assert np.array_equal(a, b), \
            (net, use_kernel, fused, batch_axis, np.abs(a - b).max())
    else:
        # a composed data axis reshapes the per-shard PRF draws, so the
        # exact truncation's +-ulp noise may differ from the stacked sim
        assert np.abs(a - b).max() <= ulp_tol * 2.0 ** -RING32.frac, \
            (net, batch_axis, np.abs(a - b).max())
        assert (a.argmax(-1) == b.argmax(-1)).all()
    print("case OK:", net, "kernel" if use_kernel else "jnp",
          "fused" if fused else "paper", batch_axis, compile_kw)


mesh3 = jax.sharding.Mesh(np.asarray(jax.devices()[:3]), ("party",))
mesh32 = jax.sharding.Mesh(np.asarray(jax.devices()[:6]).reshape(3, 2),
                           ("party", "data"))

# fc net: plain + fused-kernel paths (party-only mesh: strictly
# bit-identical — identical shapes mean identical PRF streams)
run_case("MnistNet1", (28, 28, 1), 4, False, True, mesh3)
run_case("MnistNet1", (28, 28, 1), 4, True, True, mesh3)
# conv net (Sign + fused sign-maxpool) on the kernel path
run_case("MnistNet3", (28, 28, 1), 2, True, True, mesh3)
# paper-faithful round structure: OT-based Alg 4 online
run_case("MnistNet2", (28, 28, 1), 2, False, False, mesh3)
# party axis composes with the data axis (batch sharded 2-way); per-shard
# trunc-mask draws differ from the full-batch sim, so allow ulp noise
run_case("MnistNet1", (28, 28, 1), 4, True, True, mesh32, "data",
         ulp_tol=8)
# binary-domain engine (DESIGN.md §11): public weights are replicated (not
# party-sharded) under the mesh — jnp + kernel paths, fc + conv nets
run_case("MnistNet1", (28, 28, 1), 4, False, True, mesh3, weights="public")
run_case("MnistNet1", (28, 28, 1), 4, True, True, mesh3, weights="public")
run_case("MnistNet3", (28, 28, 1), 2, True, True, mesh3, weights="public")
# binarization-unaware ablation routes post-Sign layers through the full
# arithmetic opening on both backends
run_case("MnistNet1", (28, 28, 1), 4, False, True, mesh3,
         binary_linear="off")
# depthwise-separable net (§13): the direct depthwise tap product takes
# the per-party pair's own/next views, and the public grouped kernel the
# pair stack — all three weight/engine modes, kernel and jnp pointwise
run_case("MnistNet3-sep", (28, 28, 1), 2, True, True, mesh3)
run_case("MnistNet3-sep", (28, 28, 1), 2, False, True, mesh3)
run_case("MnistNet3-sep", (28, 28, 1), 2, True, True, mesh3,
         weights="public")
run_case("MnistNet3-sep", (28, 28, 1), 2, True, True, mesh3,
         binary_linear="off")
print("OK")
"""


LEDGER_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import RING32, Parties, comm, share
from repro.core import transport
from repro.core.activation import secure_relu
from repro.core.linear import matmul_truncate
from repro.core.rss import RSS
from repro.roofline.analyze import (collective_bytes_from_hlo,
                                    ledger_vs_wire)

d, dff, T = 16, 32, 8
key = jax.random.PRNGKey(0)
rng = np.random.default_rng(0)
x = share(rng.normal(0, 0.3, (T, d)).astype(np.float32), key, RING32)
w1 = share(rng.normal(0, 0.3, (d, dff)).astype(np.float32),
           jax.random.fold_in(key, 1), RING32)
w2 = share(rng.normal(0, 0.3, (dff, d)).astype(np.float32),
           jax.random.fold_in(key, 2), RING32)
keys = Parties.setup(jax.random.PRNGKey(3)).keys


def inner(keys, xo, xn, w1o, w1n, w2o, w2n):
    t = transport.MeshTransport("party")
    with transport.use_transport(t):
        prt = Parties(keys)
        xs = RSS(t.ingest(xo, xn), RING32)
        w1s = RSS(t.ingest(w1o, w1n), RING32)
        w2s = RSS(t.ingest(w2o, w2n), RING32)
        h = matmul_truncate(xs, w1s, prt, tag="ffn.up")
        h = secure_relu(h, prt, tag="ffn.relu")
        out = matmul_truncate(h, w2s, prt, tag="ffn.down")
        return t.own_view(out.shares)


roll = lambda a: jnp.roll(a, -1, axis=0)
args = (keys, x.shares, roll(x.shares), w1.shares, roll(w1.shares),
        w2.shares, roll(w2.shares))


def check(mesh, x_spec, label, data=1):
    w_spec = P("party")
    sm = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(), x_spec, x_spec) + (w_spec,) * 4,
        out_specs=x_spec, check_vma=False)

    with comm.track() as led:
        jax.eval_shape(sm, *args)
    # the ledger traces the per-party program, so under a sharded batch it
    # meters ONE data replica's protocol; total wire = ledger x data
    assert led.nbytes + led.pre_nbytes > 0 and led.rounds == 4, led.summary()

    hlo = jax.jit(sm).lower(*args).compile().as_text()
    chk = ledger_vs_wire(hlo, led.nbytes + led.pre_nbytes,
                         data_replicas=data)
    print(label, chk)

    # every metered round exists as a real collective in the per-party HLO
    assert chk["counts"]["collective-permute"] >= 4, chk
    assert chk["counts"]["all-gather"] == 3, chk  # up/down opens + mulopen

    # bytes agree (the ledger is exact; allow header/layout slack)
    assert chk["rel_diff"] < 0.02, chk

    # sanity: the roofline per-chip extractor sees the same instructions
    colls = collective_bytes_from_hlo(hlo)
    assert (colls["collective-permute"]["count"]
            == chk["counts"]["collective-permute"])


# party-only mesh: ledger == wire, byte for byte
check(jax.sharding.Mesh(np.asarray(jax.devices()[:3]), ("party",)),
      P("party"), "party-only:")
# composed party x data mesh, batch (T) sharded 2-way: both data replicas'
# rings/gathers appear in the HLO, so wire == per-shard ledger x 2
check(jax.sharding.Mesh(np.asarray(jax.devices()[:6]).reshape(3, 2),
                        ("party", "data")),
      P("party", "data"), "party x data:", data=2)

# ---- binary-domain engine paths (DESIGN.md S11) ---------------------------
from repro.core.linear import PublicTensor, bin_matmul
from repro.core.activation import secure_sign
from repro.roofline.analyze import ledger_vs_wire

xb = share(np.where(rng.integers(0, 2, (T, d)), 1.0, -1.0)
           .astype(np.float32) * 0.25, jax.random.fold_in(key, 5), RING32)
w_pub = jnp.asarray(RING32.encode(rng.normal(0, 0.3, (d, dff))
                                  .astype(np.float32)))
w2_pub = jnp.asarray(RING32.encode(rng.normal(0, 0.3, (dff, d))
                                   .astype(np.float32)))


def inner_bin(keys, xo, xn, w1o, w1n):
    t = transport.MeshTransport("party")
    with transport.use_transport(t):
        prt = Parties(keys)
        xs = RSS(t.ingest(xo, xn), RING32)
        s = secure_sign(xs, prt, tag="sign")          # -> {0,1} scale 0
        s = s.mul_public_int(2).add_public(
            jnp.asarray(-1, jnp.int32).astype(jnp.uint32))
        w1s = RSS(t.ingest(w1o, w1n), RING32)
        h = bin_matmul(s, w1s, prt, tag="bin.up")     # reshare-only round
        h = bin_matmul(h, PublicTensor(w2_pub), prt,
                       tag="bin.down.pub")            # ZERO collectives
        # consume BOTH pair slots so DCE cannot drop the reshare ppermute
        return h.shares[0:1] + h.shares[1:2]


mesh_p = jax.sharding.Mesh(np.asarray(jax.devices()[:3]), ("party",))
args_b = (keys, xb.shares, roll(xb.shares), w1.shares, roll(w1.shares))
smb = jax.shard_map(
    inner_bin, mesh=mesh_p,
    in_specs=(P(), P("party"), P("party"), P("party"), P("party")),
    out_specs=P("party"), check_vma=False)
with comm.track() as led_b:
    jax.eval_shape(smb, *args_b)
# post-Sign shared layer: ONE reshare round, 3 elements/slot; the public
# layer records 0 bytes and compiles to NO party collectives
assert led_b.by_tag["bin.up"] == [1, 3 * T * dff * 4], led_b.summary()
assert led_b.by_tag["bin.down.pub"] == [0, 0], led_b.summary()
hlo_b = jax.jit(smb).lower(*args_b).compile().as_text()
chk = ledger_vs_wire(hlo_b, led_b.nbytes + led_b.pre_nbytes)
print("binary:", chk)
assert chk["rel_diff"] < 0.02, chk

# public-only program: the compiled per-party HLO has ZERO party
# collectives — wire bytes 0 == ledger 0
def inner_pub(keys, xo, xn):
    t = transport.MeshTransport("party")
    with transport.use_transport(t):
        prt = Parties(keys)
        xs = RSS(t.ingest(xo, xn), RING32)
        h = bin_matmul(xs, PublicTensor(jnp.asarray(w_pub)), prt,
                       tag="pub.only")
        return t.own_view(h.shares)


smp = jax.shard_map(
    inner_pub, mesh=mesh_p, in_specs=(P(), P("party"), P("party")),
    out_specs=P("party"), check_vma=False)
with comm.track() as led_p:
    jax.eval_shape(smp, keys, xb.shares, roll(xb.shares))
assert led_p.nbytes == 0 and led_p.rounds == 0, led_p.summary()
hlo_p = jax.jit(smp).lower(keys, xb.shares, roll(xb.shares)) \
    .compile().as_text()
chk_p = ledger_vs_wire(hlo_p, 0)
print("public:", chk_p)
assert chk_p["wire_bytes"] == 0 and chk_p["rel_diff"] == 0, chk_p
print("OK")
"""


def test_mesh_backend_bit_identical(tmp_path):
    """secure_infer under MeshTransport == LocalTransport, bit for bit,
    on an fc net, conv nets and a separable net, fused + paper rounds,
    kernel + jnp dots, with and without a composed data axis."""
    run_party_subprocess(EQUIV_SCRIPT, tmp_path, "mesh_equiv.py")


def test_mesh_ledger_matches_hlo_collectives(tmp_path):
    """CommLedger bytes == physical wire bytes of the ppermute/all_gather
    collectives in the compiled per-party HLO of one secure FFN layer."""
    run_party_subprocess(LEDGER_SCRIPT, tmp_path, "mesh_ledger.py")
