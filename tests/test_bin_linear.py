"""Binary-domain secure linear engine (ISSUE 4, DESIGN.md §11):
bin-shared reshare-only layers, the zero-communication bin-public path,
and the public-weight limb collapse."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import RING32, Parties, share, transport
from repro.core import linear
from repro.core.linear import PublicTensor, bin_matmul
from repro.core.rss import RSS
from repro.core.secure_model import (compile_secure, post_sign_linear_cost,
                                     secure_infer, secure_infer_cost)
from repro.kernels.bin_rss_matmul import (bin_grouped_matmul_parts,
                                          bin_grouped_matmul_ref,
                                          bin_rss_matmul_parts,
                                          bin_rss_matmul_ref,
                                          min_public_limbs,
                                          public_grouped_limbs,
                                          public_weight_limbs)
from repro.kernels.ref import grouped_rss_matmul_ref
from repro.nn import bnn
from test_secure_model import _grid_input, _random_net_params


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n,wmag", [
    (128, 128, 128, 1),      # 1-limb (binarized-scale) weights
    (256, 128, 384, 3000),   # 2-limb
    (64, 96, 32, 300000),    # 3-limb
    (33, 17, 5, 8),          # non-tile-aligned
    (64, 128, 32, 32767),    # balanced-digit boundary: 0x7FFF needs 3 limbs
])
def test_bin_rss_matmul_kernel_exact(m, k, n, wmag):
    """Public-weight kernel == reference == RSS identity, bit-exact mod
    2^32, at every adaptive limb count."""
    key = jax.random.PRNGKey(m + 7 * k + 13 * n)
    xs = jax.random.bits(key, (3, m, k), jnp.uint32)
    w = (jax.random.randint(jax.random.fold_in(key, 1), (k, n),
                            -wmag, wmag + 1)
         .astype(jnp.int32).astype(jnp.uint32))
    wl = public_weight_limbs(w)
    got = np.asarray(bin_rss_matmul_parts(xs, wl, min_dim=1))
    ref = np.asarray(bin_rss_matmul_ref(xs, wl))
    assert np.array_equal(got, ref)
    # Σ_s z_s == (Σ x_s) @ W mod 2^32 — a valid RSS of x @ W, rebuilt with
    # zero communication
    tot = (got[0] + got[1] + got[2]).astype(np.uint32)
    want = np.asarray(jax.lax.dot_general(
        xs.sum(0), w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.uint32))
    assert np.array_equal(tot, want)


def test_bin_kernel_pair_stack():
    """The MeshTransport layout: a per-party (2, M, K) pair stack — every
    held slot's product is local (the RSS pair is rebuilt on-device)."""
    key = jax.random.PRNGKey(0)
    xs = jax.random.bits(key, (2, 128, 128), jnp.uint32)
    w = (jax.random.randint(key, (128, 128), -5, 6)
         .astype(jnp.int32).astype(jnp.uint32))
    wl = public_weight_limbs(w)
    assert np.array_equal(np.asarray(bin_rss_matmul_parts(xs, wl)),
                          np.asarray(bin_rss_matmul_ref(xs, wl)))


def test_public_limb_collapse():
    """The §11 collapse: public bounded encodings need 1–3 limbs; a share
    (uniform mod 2^32) always needs 4.  Binarized ±1 weights hit L=1."""
    ring = RING32
    pm1 = np.asarray(ring.encode(np.asarray([-1.0, 1.0])), np.uint32)
    bin_w = np.where(np.arange(64 * 64).reshape(64, 64) % 2, 1, -1)
    assert min_public_limbs(np.asarray(bin_w, np.int64)
                            .astype(np.uint32)) == 1          # ±1, scale 0
    assert min_public_limbs(pm1) == 2                         # ±1 at f=12
    w = ring.encode(np.random.default_rng(0).normal(0, 0.5, (64, 64)))
    assert min_public_limbs(np.asarray(w)) <= 3               # typical fp
    full = np.asarray(jax.random.bits(jax.random.PRNGKey(1), (64, 64),
                                      jnp.uint32))
    assert min_public_limbs(full) == 4                        # share-like
    # balanced digits top out at +127: values just under a power-of-two
    # boundary spill a carry into the next limb (0x7FFF -> [-1,-128,1])
    assert min_public_limbs(np.asarray([32767], np.uint32)) == 3
    assert min_public_limbs(np.asarray([127], np.uint32)) == 1
    assert min_public_limbs(np.asarray([128], np.uint32)) == 2

    # compile-time cache uses the minimal count
    params = _random_net_params("MnistNet1")
    model = compile_secure(params, "MnistNet1", jax.random.PRNGKey(0),
                           RING32, use_kernel_dot=True, weights="public")
    lin = [op for op in model.ops if op["op"] == "fc"]
    assert lin and all(op["pub_w"][0].limbs is not None for op in lin)
    assert all(op["pub_w"][0].limbs.n_limbs <= 3 for op in lin)


# ---------------------------------------------------------------------------
# Grouped (depthwise) products — the sepconv half of the §13 pipeline
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("c,k,mult,hw,stride,padding", [
    (16, 5, 1, 14, 1, 2),   # MnistNet3-sep shape (5×5 depthwise)
    (4, 3, 1, 8, 1, 1),     # 3×3 depthwise
    (3, 3, 2, 11, 2, 0),    # channel multiplier > 1, strided
    (3, 3, 1, 32, 1, 1),    # CifarNet2's first layer
])
def test_tap_product_reconstructs_depthwise_conv(c, k, mult, hw, stride,
                                                 padding):
    """The served shared-weight depthwise product: Σ_s z_s of
    `_grouped_conv_parts` is the plain depthwise convolution of Σx and Σw
    mod 2^32, on shares drawn from the whole ring."""
    key = jax.random.PRNGKey(c + 7 * k + 13 * hw)
    b = 2
    x = RSS(jax.random.bits(key, (3, b, hw, hw, c), jnp.uint32), RING32)
    w = RSS(jax.random.bits(jax.random.fold_in(key, 1),
                            (3, k, k, 1, c * mult), jnp.uint32), RING32)
    z = np.asarray(linear._grouped_conv_parts(x, w, stride, padding, c))
    got = z.sum(0, dtype=np.uint32)
    xp = np.pad(np.asarray(x.shares).sum(0, dtype=np.uint32),
                ((0, 0), (padding, padding), (padding, padding), (0, 0)))
    wp = np.asarray(w.shares).sum(0, dtype=np.uint32)[:, :, 0]
    ho = (hw + 2 * padding - k) // stride + 1
    want = np.zeros((b, ho, ho, c * mult), np.uint32)
    for i in range(k):
        for j in range(k):
            tap = xp[:, i:i + stride * ho:stride, j:j + stride * ho:stride]
            # output channel o reads input channel o // mult
            want += np.repeat(tap, mult, axis=-1) * wp[i, j]
    assert got.shape == want.shape
    assert np.array_equal(got, want)


@pytest.mark.parametrize("wmag", [1, 3000, 300000, None])  # L = 1/2/3/4
def test_grouped_public_kernel_exact(wmag):
    """Public grouped kernel at every adaptive limb count: == reference,
    and Σ_s z_s[c] rebuilds x[c] @ W[c] with zero communication."""
    key = jax.random.PRNGKey(0 if wmag is None else wmag)
    c, m, k = 8, 160, 25
    xs = jax.random.bits(key, (3, c, m, k), jnp.uint32)
    if wmag is None:    # share-like uniform weight: needs all 4 limbs
        w = jax.random.bits(jax.random.fold_in(key, 1), (c, k, 1), jnp.uint32)
    else:
        w = (jax.random.randint(jax.random.fold_in(key, 1), (c, k, 1),
                                -wmag, wmag + 1)
             .astype(jnp.int32).astype(jnp.uint32))
    wl = public_grouped_limbs(w)
    got = np.asarray(bin_grouped_matmul_parts(xs, wl, min_dim=1))
    ref = np.asarray(bin_grouped_matmul_ref(xs, wl))
    assert np.array_equal(got, ref)
    tot = (got[0] + got[1] + got[2]).astype(np.uint32)
    want = np.asarray(jax.lax.dot_general(
        xs.sum(0), w, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.uint32))
    assert np.array_equal(tot, want)


# ---------------------------------------------------------------------------
# End-to-end paths (LocalTransport; the Mesh backend equivalence is pinned
# by tests/test_transport_mesh.py on the same modes)
# ---------------------------------------------------------------------------

def _run_net(params, net, x, **kw):
    model = compile_secure(params, net, jax.random.PRNGKey(2), RING32, **kw)
    out = secure_infer(model, share(x, jax.random.PRNGKey(4), RING32),
                       Parties.setup(jax.random.PRNGKey(3)))
    return np.asarray(out), model


@pytest.mark.parametrize("net,shape,batch", [
    ("MnistNet1", (28, 28, 1), 8),
    ("CifarNet2", (32, 32, 3), 2),
])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_bin_engine_bit_identical_to_arith_route(net, shape, batch,
                                                 use_kernel):
    """The bin-shared engine must be BIT-identical to the generic Alg-2
    arithmetic routing on post-Sign layers: same additive products mod
    2^32, same PRF draw order, bias riding the parts instead of the full
    RSS — kernel and jnp dots, fc and sepconv nets."""
    params = _random_net_params(net)
    x = _grid_input((batch,) + shape)
    got, _ = _run_net(params, net, x, use_kernel_dot=use_kernel)
    ref, _ = _run_net(params, net, x, use_kernel_dot=use_kernel,
                      binary_linear="generic")
    assert np.array_equal(got, ref)


def _im2col_grouped_parts(x, w, stride, padding, groups):
    """The per-channel reference route of the depthwise product: im2col
    patches, then each channel's batched dot against its own kernel
    (`grouped_rss_matmul_ref`), on the transport's own/next views."""
    kh, kw, _, cout = (int(d) for d in w.shape)
    b, cin = int(x.shape[0]), int(x.shape[3])
    mult = cout // groups
    t = transport.current()
    cols, ho, wo = linear._im2col_rss(x, kh, kw, stride, padding)

    def fold(a):    # (S, B, Ho, Wo, K*C) -> (S, C, M, K)
        return a.reshape(a.shape[0], -1, kh * kw, cin).transpose(0, 3, 1, 2)
    wst = t.own_view(w.shares).reshape(-1, kh * kw, cin, mult)
    wn = t.next_view(w.shares).reshape(-1, kh * kw, cin, mult)
    z = grouped_rss_matmul_ref(fold(t.own_view(cols.shares)),
                               wst.transpose(0, 2, 1, 3),
                               (wst + wn).transpose(0, 2, 1, 3),
                               fold(t.next_view(cols.shares)))
    return z.transpose(0, 2, 1, 3).reshape(z.shape[0], b, ho, wo, cout)


@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("padding", [0, 1])
@pytest.mark.parametrize("k", [3, 5])
@pytest.mark.parametrize("mult", [1, 2])
def test_direct_depthwise_matches_im2col_reference(stride, padding, k, mult):
    """The direct tap product of `_grouped_conv_parts` equals im2col +
    `grouped_rss_matmul_ref` bit for bit, on shares drawn from the whole
    ring: strides, paddings, kernel sizes, depthwise multipliers."""
    key = jax.random.PRNGKey(100 * stride + 10 * padding + k + mult)
    c, b, hw = 3, 2, 11
    x = RSS(jax.random.bits(key, (3, b, hw, hw, c), jnp.uint32), RING32)
    w = RSS(jax.random.bits(jax.random.fold_in(key, 1),
                            (3, k, k, 1, c * mult), jnp.uint32), RING32)
    got = np.asarray(linear._grouped_conv_parts(x, w, stride, padding, c))
    ref = np.asarray(_im2col_grouped_parts(x, w, stride, padding, c))
    ho = (hw + 2 * padding - k) // stride + 1
    assert got.shape == (3, b, ho, ho, c * mult)
    assert np.array_equal(got, ref)


def test_sepconv_grouped_kernel_bit_identical(monkeypatch):
    """The served sepconv net (direct depthwise tap product, kernel path
    on) is bit-identical to the same net with its depthwise halves on the
    per-channel reference route (im2col + batched dots): same additive
    parts mod 2^32, same single reshare, same PRF draw order."""
    params = _random_net_params("MnistNet3-sep")
    x = _grid_input((2, 28, 28, 1))
    served, _ = _run_net(params, "MnistNet3-sep", x, use_kernel_dot=True)
    monkeypatch.setattr(linear, "_grouped_conv_parts", _im2col_grouped_parts)
    ref, _ = _run_net(params, "MnistNet3-sep", x, use_kernel_dot=True)
    assert np.array_equal(served, ref)


@pytest.mark.parametrize("net,shape,exact", [
    ("MnistNet1", (28, 28, 1), True),
    ("CifarNet2", (32, 32, 3), False),
    ("MnistNet3-sep", (28, 28, 1), False),
])
def test_public_weights_match_plaintext_and_kernel(net, shape, exact):
    """weights="public" computes the same function (grid-margin exact on
    MnistNet1; statistical bounds on the deep separable net), and the
    public kernel path is bit-identical to the public jnp path."""
    params = _random_net_params(net)
    x = _grid_input((2,) + shape)
    plain, _ = bnn.bnn_forward(params, jnp.asarray(x), net, train=False)
    want = np.asarray(plain, np.float32)
    got, _ = _run_net(params, net, x, weights="public")
    gotk, _ = _run_net(params, net, x, weights="public",
                       use_kernel_dot=True)
    assert np.array_equal(got, gotk)
    err = np.abs(got - want)
    if exact:
        assert err.max() < 0.05
    else:
        assert np.isfinite(got).all()
        assert np.median(err) < 0.3 and err.max() < 8.0


@pytest.mark.parametrize("net,shape", [
    ("MnistNet1", (28, 28, 1)),
    ("CifarNet1", (32, 32, 3)),
])
def test_postsign_wire_byte_reduction(net, shape):
    """Acceptance pin: the binary-domain engine spends ≥40% fewer wire
    bytes on post-Sign linear layers than the binarization-unaware
    arithmetic routing, and the public-weight mode spends ZERO there.
    (fc/conv nets: separable convs would keep the depthwise→pointwise
    seam truncation even under public weights — DESIGN.md §11.)"""
    params = _random_net_params(net)
    key = jax.random.PRNGKey(0)

    def ledger(**kw):
        model = compile_secure(params, net, key, RING32, **kw)
        return model, secure_infer_cost(model, (1,) + shape)

    m_bin, led_bin = ledger()
    m_off, led_off = ledger(binary_linear="off")
    m_pub, led_pub = ledger(weights="public")

    b_bin, _ = post_sign_linear_cost(m_bin, led_bin)
    b_off, _ = post_sign_linear_cost(m_off, led_off)
    b_pub, r_pub = post_sign_linear_cost(m_pub, led_pub)
    assert b_off > 0
    assert b_bin <= 0.6 * b_off, (b_bin, b_off)   # 50% by construction
    assert b_pub == 0 and r_pub == 0, (b_pub, r_pub)

    # whole-net trajectory: arith > binary > public, rounds never worse
    assert led_bin.nbytes < led_off.nbytes
    assert led_pub.nbytes < led_bin.nbytes
    assert led_pub.rounds < led_bin.rounds <= led_off.rounds


def test_sepconv_depthwise_wire_costs():
    """Depthwise as a first-class secure path (MnistNet3-sep):

    * binary engine: the post-Sign depthwise is ONE reshare —
      3 ring elements/output, no truncation opening (no dwtrunc tag);
    * arith ablation: the same reshare PLUS the truncation opening
      (2× the depthwise bytes), post-Sign total ≥20% worse than binary
      (sepconv = 9n vs 12n elements, DESIGN.md §11/§13);
    * public weights: the post-Sign depthwise is ZERO rounds/bytes."""
    net, shape = "MnistNet3-sep", (28, 28, 1)
    params = _random_net_params(net)
    key = jax.random.PRNGKey(0)

    def ledger(**kw):
        model = compile_secure(params, net, key, RING32, **kw)
        return model, secure_infer_cost(model, (1,) + shape)

    m_bin, led_bin = ledger()
    m_off, led_off = ledger(binary_linear="off")
    m_pub, led_pub = ledger(weights="public")

    dw = lambda led: {t: v for t, v in led.by_tag.items()
                      if ".dw" in t and not t.startswith("pre:")}
    dw_bin, dw_off, dw_pub = dw(led_bin), dw(led_off), dw(led_pub)

    # bin engine: exactly one dw entry, the .bin reshare — 3 elements per
    # depthwise output (14×14×16 after conv+maxpool), 1 round
    (tag_bin, (r_bin, b_bin_dw)), = dw_bin.items()
    assert tag_bin.endswith(".dwconv.bin") and r_bin == 1
    assert b_bin_dw == 3 * (14 * 14 * 16) * 4, b_bin_dw

    # ablation: same reshare bytes + an equal-sized truncation opening
    assert sum(b for _, b in dw_off.values()) == 2 * b_bin_dw, dw_off
    assert any(t.endswith(".dwtrunc") for t in dw_off)

    # public: the depthwise records a visible zero
    (tag_pub, cost_pub), = dw_pub.items()
    assert tag_pub.endswith(".dwconv.pub") and cost_pub == [0, 0]

    # post-Sign totals: binary ≥20% under arith; public keeps only the
    # pointwise truncation opening (nonzero — the dw→pw seam, §11)
    b_bin, _ = post_sign_linear_cost(m_bin, led_bin)
    b_off, _ = post_sign_linear_cost(m_off, led_off)
    b_pub, _ = post_sign_linear_cost(m_pub, led_pub)
    assert b_off > 0
    assert b_bin <= 0.8 * b_off, (b_bin, b_off)
    assert 0 < b_pub < b_bin
    assert led_pub.nbytes < led_bin.nbytes < led_off.nbytes


def test_public_mode_zero_linear_ledger_entries():
    """Every public linear layer records a visible 0-byte / 0-round ledger
    entry (the protocol table shows the layer; the wire stays empty), and
    the only linear-tagged online traffic left is the first layer's
    truncation opening."""
    params = _random_net_params("MnistNet1")
    model = compile_secure(params, "MnistNet1", jax.random.PRNGKey(0),
                           RING32, weights="public")
    led = secure_infer_cost(model, (1, 28, 28, 1))
    pub_tags = {t for t in led.by_tag if t.endswith(".pub")}
    assert pub_tags == {"l1.fc.pub", "l3.fc.pub", "l5.fc.pub"}, pub_tags
    assert all(led.by_tag[t] == [0, 0] for t in pub_tags)
    lin_traffic = {t: v for t, v in led.by_tag.items()
                   if t.startswith("l") and v[1] > 0}
    assert set(lin_traffic) == {"l1.trunc"}, lin_traffic


def test_bin_matmul_public_tensor_direct():
    """Unit-level: bin_matmul with a PublicTensor reconstructs x @ W
    exactly and records zero bytes."""
    from repro.core import comm
    from repro.core.rss import reconstruct

    rng = np.random.default_rng(0)
    x = np.where(rng.integers(0, 2, (16, 24)), 1.0, -1.0)  # ±1, scale 0
    w = rng.normal(0, 0.5, (24, 8)).astype(np.float32)
    ring = RING32
    # ±1 at scale 0: share the integer encoding directly
    xs = share(np.asarray(x, np.int64).astype(np.uint32),
               jax.random.PRNGKey(1), ring, encoded=True)
    parties = Parties.setup(jax.random.PRNGKey(2))
    pw = PublicTensor(jnp.asarray(ring.encode(w)),
                      public_weight_limbs(jnp.asarray(ring.encode(w))))
    with comm.track() as led:
        z = bin_matmul(xs, pw, parties, tag="unit")
    assert led.nbytes == 0 and led.rounds == 0
    got = np.asarray(ring.decode(reconstruct(z, decode=False)))
    assert np.abs(got - x @ w).max() < 1e-3