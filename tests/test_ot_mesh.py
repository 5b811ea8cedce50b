"""`core/ot.py::ot3` under MeshTransport — previously only exercised
indirectly through the MSB/activation protocols: exactness of the 1-of-3
selection per party program, and the ledger's bytes against the compiled
per-party HLO's ppermute wire bytes.

Runs in a subprocess with 8 fake host devices (same pattern as
test_transport_mesh.py)."""
from conftest import run_party_subprocess

OT_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import RING32, Parties, comm, share_bits, transport
from repro.core.ot import ot3
from repro.roofline.analyze import ledger_vs_wire, party_wire_bytes_from_hlo

N = 64
rng = np.random.default_rng(0)
m0 = rng.integers(0, 1 << 32, N, dtype=np.uint32)
m1 = rng.integers(0, 1 << 32, N, dtype=np.uint32)
c = rng.integers(0, 2, N).astype(np.uint8)
cb = share_bits(c, jax.random.PRNGKey(1))     # XOR shares of the choice
keys = Parties.setup(jax.random.PRNGKey(3)).keys

ROLES = [  # (sender, receiver, helper): every rotation of the triangle
    (1, 0, 2), (0, 2, 1), (2, 1, 0)]


def make_inner(sender, receiver, helper):
    def inner(keys, m0, m1, cb_own, cb_nxt):
        t = transport.MeshTransport("party")
        with transport.use_transport(t):
            prt = Parties(keys)
            shares = t.ingest(cb_own, cb_nxt)
            # the choice slot is the share the sender does not hold
            slot = (sender + 2) % 3
            mc = ot3(m0, m1, shares, slot, sender=sender,
                     receiver=receiver, helper=helper, parties=prt,
                     ring=RING32, tag="ot3")
            return mc[None]
    return inner


mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:3]), ("party",))
roll = lambda a: jnp.roll(a, -1, axis=0)

for sender, receiver, helper in ROLES:
    # the plain choice bit for this OT is the xor of all three shares,
    # but the protocol consumes only the slot the sender is missing
    sm = jax.shard_map(
        make_inner(sender, receiver, helper), mesh=mesh,
        in_specs=(P(), P(), P(), P("party"), P("party")),
        out_specs=P("party"), check_vma=False)
    args = (keys, jnp.asarray(m0), jnp.asarray(m1), cb.shares,
            roll(cb.shares))

    with comm.track() as led:
        jax.eval_shape(sm, *args)
    # Alg 1: 2 sequential rounds, 3 ring elements per slot
    assert led.by_tag["ot3"] == [2, 3 * N * 4], led.summary()

    out = np.asarray(jax.jit(sm)(*args))   # (3, N): one row per party
    got = out[receiver]
    # the ideal functionality selects by the choice-slot tensor (the
    # share the sender is missing, known to receiver + helper)
    cslot = np.asarray(cb.shares)[(sender + 2) % 3]
    want = np.where(cslot.astype(bool), m1, m0)
    assert np.array_equal(got, want), (sender, receiver, helper)

    # ledger bytes == compiled ppermute wire bytes (each of the 3 sends
    # is one single-pair collective-permute of N ring elements)
    hlo = jax.jit(sm).lower(*args).compile().as_text()
    wire = party_wire_bytes_from_hlo(hlo)
    assert wire["collective-permute"]["bytes"] == 3 * N * 4, wire
    assert wire["collective-permute"]["count"] == 3, wire
    assert wire["all-gather"]["bytes"] == 0, wire
    chk = ledger_vs_wire(hlo, led.nbytes)
    assert chk["rel_diff"] == 0.0, chk
    print("role OK:", (sender, receiver, helper))

print("OK")
"""


def test_ot3_mesh_selection_and_wire_bytes(tmp_path):
    """ot3 under MeshTransport: the receiver's program reconstructs m_c
    exactly for every role rotation, the ledger meters 2 rounds / 3
    elements per slot, and those bytes equal the compiled per-party
    HLO's three single-pair ppermutes."""
    run_party_subprocess(OT_SCRIPT, tmp_path, "ot_mesh.py")
