"""Symbolic cost model + deployment path solver (DESIGN.md §15).

The load-bearing contract: `cost_model.model_cost` predicts the live
CommLedger **byte-exactly** for every net / weight mode / routing mode /
batch / fusing state — the closed-form table and the protocol stack can
never drift apart silently.  On top of that, the solver's assignments
must reproduce the legacy §11 path labels (ties keep the historical
preference order), the per-op ``engine`` override must actually steer
the executor, and no file outside the repo may steer a build.
"""
import json

import jax
import numpy as np
import pytest

from repro.core import RING32, cost_model
from repro.core.linear import set_fused_rounds
from repro.core.secure_model import (compile_secure, secure_infer,
                                     secure_infer_cost)
from repro.core.randomness import Parties
from repro.core.rss import share
from repro.nn.bnn import INPUT_SHAPES, init_bnn

NETS = ["MnistNet1", "CifarNet1", "MnistNet3-sep", "CifarNet2"]
MODES = [
    {"weights": "shared", "binary_linear": "auto"},
    {"weights": "shared", "binary_linear": "generic"},
    {"weights": "shared", "binary_linear": "off"},
    {"weights": "public"},
]


def _model(net, **kw):
    params = init_bnn(jax.random.PRNGKey(0), net)
    return compile_secure(params, net, jax.random.PRNGKey(1), RING32, **kw)


def _assert_exact(model, shape):
    led = secure_infer_cost(model, shape)
    rep = cost_model.model_cost(model, shape)
    assert (rep.rounds, rep.nbytes) == (led.rounds, led.nbytes), \
        (model.net, model.weights, model.binary_linear, shape)
    assert (rep.pre_rounds, rep.pre_nbytes) == \
        (led.pre_rounds, led.pre_nbytes), (model.net, shape)
    return rep, led


@pytest.mark.parametrize("kw", MODES,
                         ids=["auto", "generic", "off", "public"])
@pytest.mark.parametrize("net", NETS)
def test_ledger_fidelity(net, kw):
    """Predicted rounds == ledger rounds and predicted bytes == CommLedger
    bytes, exactly, for every net/path in the zoo."""
    _assert_exact(_model(net, **kw), (1,) + INPUT_SHAPES[net])


def test_ledger_fidelity_batch_scaling():
    model = _model("MnistNet1")
    rep1, _ = _assert_exact(model, (1,) + INPUT_SHAPES["MnistNet1"])
    rep4, _ = _assert_exact(model, (4,) + INPUT_SHAPES["MnistNet1"])
    # traffic is per-element, rounds are per-layer
    assert rep4.nbytes == 4 * rep1.nbytes
    assert rep4.rounds == rep1.rounds


@pytest.mark.parametrize("kw", [MODES[0], MODES[3]], ids=["auto", "public"])
def test_ledger_fidelity_unfused(kw):
    """The paper-faithful round structure (set_fused_rounds(False)) has its
    own closed forms — exact there too, including the sepconv halves."""
    model = _model("MnistNet3-sep", **kw)
    set_fused_rounds(False)
    try:
        _assert_exact(model, (1,) + INPUT_SHAPES["MnistNet3-sep"])
    finally:
        set_fused_rounds(True)


def test_deployment_registry():
    assert set(cost_model.DEPLOYMENTS) == {"local", "lan", "wan"}
    assert cost_model.resolve_deployment(None) is None
    assert cost_model.resolve_deployment("WAN") is cost_model.WAN
    d = cost_model.resolve_deployment(cost_model.LAN)
    assert d is cost_model.LAN
    b = cost_model.LAN.with_batch(32)
    assert b.batch == 32 and b.network is cost_model.LAN.network
    with pytest.raises(ValueError, match="lan, local, wan"):
        cost_model.resolve_deployment("mars")


def test_cost_time_weighting():
    """WAN's 80 ms RTT dominates rounds; local is compute-only."""
    c = cost_model.Cost(rounds=6, nbytes=10_000, flops=10**9)
    assert c.time(cost_model.WAN) > c.time(cost_model.LAN)
    assert c.time(cost_model.LOCAL) == pytest.approx(
        10**9 / cost_model.LOCAL.compute_int8_ops)


@pytest.mark.parametrize("net", ["MnistNet3-sep", "CifarNet1"])
def test_solver_label_stability(net):
    """The solver's assignment reproduces the legacy fixed-preference
    labels under every registry deployment (cost ties keep list order)."""
    legacy = [op["path"] for op in _model(net).ops
              if op["op"] in ("conv", "sepconv", "fc")]
    for dep in (None, "local", "lan", "wan"):
        got = [op["path"] for op in _model(net, deployment=dep).ops
               if op["op"] in ("conv", "sepconv", "fc")]
        assert got == legacy, dep


def test_predicted_report_rides_on_model():
    model = _model("MnistNet1", deployment="lan")
    rep = model.predicted
    assert isinstance(rep, cost_model.CostReport)
    assert model.deployment == "lan"
    # per-op stamps agree with the report and with a fresh recompute
    fresh = cost_model.model_cost(model, (1,) + INPUT_SHAPES["MnistNet1"])
    assert (fresh.rounds, fresh.nbytes) == (rep.rounds, rep.nbytes)
    for op in model.ops:
        if op["op"] in ("conv", "sepconv", "fc"):
            assert op["cost"]["path"] == str(op["path"])
            assert op["cost"]["rounds"] >= 0
            assert "alternatives" in op["cost"]


def test_engine_override_steers_executor():
    """A per-op ``engine`` stamp overrides the model-wide routing: the
    generic Alg-2 route replaces the bin-shared reshare (same cost, same
    values, different ledger tags)."""
    model = _model("MnistNet1")
    bin_idxs = [i for i, op in enumerate(model.ops)
                if op["op"] == "fc" and op.get("path") == "bin-shared"]
    assert bin_idxs
    led = secure_infer_cost(model, (1,) + INPUT_SHAPES["MnistNet1"])
    assert f"l{bin_idxs[0]}.fc.bin" in led.by_tag
    model.ops[bin_idxs[0]]["engine"] = False
    led2 = secure_infer_cost(model, (1,) + INPUT_SHAPES["MnistNet1"])
    assert f"l{bin_idxs[0]}.fc" in led2.by_tag
    assert f"l{bin_idxs[0]}.fc.bin" not in led2.by_tag
    # generic route is the bit-identity reference: same totals
    assert (led2.rounds, led2.nbytes) == (led.rounds, led.nbytes)


def test_kernel_requests_shapes():
    model = _model("MnistNet1")
    reqs = cost_model.model_cost(
        model, (8,) + INPUT_SHAPES["MnistNet1"]).kernel_requests()
    assert reqs == [("rss_matmul", 8, 784, 128, 4, None),
                    ("rss_matmul", 8, 128, 128, 4, None),
                    ("rss_matmul", 8, 128, 10, 4, None)]
    # batch 1 fc layers (M=1) fall below the kernel tile threshold
    assert cost_model.model_cost(
        model, (1,) + INPUT_SHAPES["MnistNet1"]).kernel_requests() == []


def test_sepconv_kernel_requests_by_weight_mode():
    """A shared-weight depthwise half is a direct tap product and requests
    no kernel launch; a public one still runs the grouped public-limb
    kernel.  The pointwise halves request their dense launch either way."""
    net, batch = "MnistNet3-sep", 8
    shape = (batch,) + INPUT_SHAPES[net]
    shared = cost_model.model_cost(_model(net, use_kernel_dot=True),
                                   shape).kernel_requests()
    public = cost_model.model_cost(
        _model(net, use_kernel_dot=True, weights="public"),
        shape).kernel_requests()
    n_sep = sum(op["op"] == "sepconv" for op in _model(net).ops)
    assert n_sep > 0
    assert not [r for r in shared if "grouped" in r[0]]
    assert [r[0] for r in public].count("bin_grouped_matmul") == n_sep
    assert ([r for r in shared if r[0] == "rss_matmul"]
            and [r for r in public if r[0] == "bin_rss_matmul"])


def test_build_reads_no_kernel_cache(tmp_path, monkeypatch):
    """A build reads no kernel-schedule file: with ``HOME`` and
    ``REPRO_AUTOTUNE_CACHE`` both leading to an old autotune cache that
    would force every launch onto the jnp lowering, the kernel-path model
    carries no ``kcfg`` on any op and answers bit for bit as a build made
    without them."""
    net, batch = "MnistNet1", 8
    shape = (batch,) + INPUT_SHAPES[net]
    params = init_bnn(jax.random.PRNGKey(0), net)
    dep = cost_model.LAN.with_batch(batch)

    def build():
        return compile_secure(params, net, jax.random.PRNGKey(1), RING32,
                              use_kernel_dot=True, deployment=dep)

    clean = build()
    reqs = cost_model.model_cost(clean, shape).kernel_requests()
    assert reqs
    tile = lambda d: -(-d // 128) * 128
    entries = {f"{f}.m{tile(m)}k{tile(k)}n{tile(n)}.L{l}."
               f"{jax.default_backend()}":
               {"bm": 128, "bn": 128, "bk": 128, "lowering": "ref",
                "us": 1.0, "default_us": 2.0, "space": "test"}
               for f, m, k, n, l, _ in reqs}
    cache = json.dumps({"version": 1, "entries": entries})
    (tmp_path / ".cache" / "repro").mkdir(parents=True)
    (tmp_path / ".cache" / "repro" / "autotune.json").write_text(cache)
    (tmp_path / "autotune.json").write_text(cache)
    monkeypatch.setenv("HOME", str(tmp_path))
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))

    planted = build()
    assert not [op for op in planted.ops if "kcfg" in op]
    x = np.random.default_rng(0).integers(0, 2, shape).astype(np.float32) \
        - 0.5
    xs = share(x, jax.random.PRNGKey(3), RING32)
    parties = Parties.setup(jax.random.PRNGKey(7))
    assert np.array_equal(np.asarray(secure_infer(clean, xs, parties)),
                          np.asarray(secure_infer(planted, xs, parties)))


# ---------------------------------------------------------------------------
# Attention-path closed forms (DESIGN.md §16): the lm_* formulas must track
# the live CommLedger byte-exactly, like model_cost does for the BNN zoo
# ---------------------------------------------------------------------------

def _lm_block_ledger(seq, fused, customized):
    import jax.numpy as jnp  # noqa: F401
    from repro.core import comm
    from repro.core.secure_transformer import secure_block, share_block_params

    bp, _ = share_block_params(jax.random.PRNGKey(0), 32, 2, 64)
    x = share(np.random.default_rng(1).normal(0, 0.5, (seq, 32))
              .astype(np.float32), jax.random.PRNGKey(2))
    set_fused_rounds(fused)
    try:
        return comm.estimate_cost(
            lambda s: secure_block(
                s, bp, Parties.setup(jax.random.PRNGKey(5)),
                customized=customized), x)
    finally:
        set_fused_rounds(True)


@pytest.mark.parametrize("customized", [True, False],
                         ids=["custom", "softmax"])
@pytest.mark.parametrize("fused", [True, False], ids=["fused", "paper"])
@pytest.mark.parametrize("seq", [8, 16, 32])
def test_lm_block_cost_byte_exact(seq, fused, customized):
    """lm_block_cost == live ledger of secure_block, for both attention
    modes, both round structures, seq ∈ {8, 16, 32}."""
    led = _lm_block_ledger(seq, fused, customized)
    pred = cost_model.lm_block_cost(seq, seq, 32, 2, 64, fused=fused,
                                    customized=customized)
    assert (pred.rounds, pred.nbytes) == (led.rounds, led.nbytes), \
        (seq, fused, customized, pred, led.summary())


@pytest.mark.parametrize("static_norm", [False, True],
                         ids=["rmsnorm", "staticnorm"])
@pytest.mark.parametrize("customized", [True, False],
                         ids=["custom", "softmax"])
@pytest.mark.parametrize("fused", [True, False], ids=["fused", "paper"])
def test_lm_step_cost_byte_exact(fused, customized, static_norm):
    """lm_step_cost == live ledger of one secure_decode_step against a
    bucket-16 cache (the comm-per-token number serving reports), including
    the static-norm customization (zero norm rounds)."""
    import jax.numpy as jnp
    from repro.core import comm
    from repro.core.secure_transformer import (init_kv_cache,
                                               secure_decode_step,
                                               share_lm_params)

    lm, _ = share_lm_params(jax.random.PRNGKey(0), 32, 32, 2, 64, 2, RING32)
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    set_fused_rounds(fused)
    try:
        led = comm.estimate_cost(
            lambda c, t, p, k: secure_decode_step(lm, c, t, p, k, customized,
                                                  static_norm),
            init_kv_cache(2, 2, 16, 16, RING32),
            jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32), keys)
    finally:
        set_fused_rounds(True)
    pred = cost_model.lm_step_cost(16, 32, 2, 64, 2, 32, fused=fused,
                                   customized=customized,
                                   static_norm=static_norm)
    assert (pred.rounds, pred.nbytes) == (led.rounds, led.nbytes), \
        (fused, customized, static_norm, pred, led.summary())


def test_lm_cost_scaling():
    """Closed-form scaling laws the serving design rests on: customized
    decode rounds are bucket-independent (ReLU-attention has no tournament),
    softmax rounds grow with the bucket, and per-block bytes scale linearly
    in the score count."""
    kw = dict(d=32, heads=2, d_ff=64, n_blocks=2, vocab=32)
    r8 = cost_model.lm_step_cost(8, **kw, customized=True)
    r32 = cost_model.lm_step_cost(32, **kw, customized=True)
    assert r8.rounds == r32.rounds
    assert r32.nbytes > r8.nbytes
    s8 = cost_model.lm_step_cost(8, **kw, customized=False)
    s32 = cost_model.lm_step_cost(32, **kw, customized=False)
    assert s32.rounds > s8.rounds
    # the custom-vs-softmax gap (the paper's Table-2 claim, LM workload)
    assert r8.rounds < s8.rounds and r8.nbytes < s8.nbytes
    # attention bytes are linear in heads at fixed (q, kv)
    c1 = cost_model.lm_block_cost(1, 16, 32, 1, 64)
    c2 = cost_model.lm_block_cost(1, 16, 32, 2, 64)
    c4 = cost_model.lm_block_cost(1, 16, 32, 4, 64)
    assert c4.nbytes - c2.nbytes == 2 * (c2.nbytes - c1.nbytes)


def test_report_properties():
    model = _model("CifarNet2", weights="public")
    rep = cost_model.model_cost(model, (1,) + INPUT_SHAPES["CifarNet2"])
    assert rep.total.rounds == sum(e.cost.rounds for e in rep.entries)
    assert rep.total.nbytes == sum(e.cost.nbytes for e in rep.entries)
    assert rep.entries[-1].name == "output"
    # offline material is path-invariant: only MSB sites generate it
    assert rep.pre_nbytes > 0
    # flops flow from the linear layers only
    assert rep.flops == sum(e.cost.flops for e in rep.entries
                            if e.name.startswith("l"))
    d = cost_model.LAN
    assert rep.time(d) == pytest.approx(
        d.network.time(rep.rounds, rep.nbytes) + rep.flops
        / d.compute_int8_ops)
    budget = cost_model.LAN.with_batch(1)
    assert rep.within_offline_budget(budget) is None
    tight = cost_model.DeploymentDescriptor(
        "t", budget.network, offline_budget_mb=1e-9)
    assert rep.within_offline_budget(tight) is False
