"""Mixture-of-Experts with capacity-based dispatch (TPU/GSPMD-idiomatic).

Experts are stacked on a leading E axis and sharded over the "model" mesh
axis (expert parallelism); dispatch/combine are scatter/gather einsums whose
cross-shard traffic lowers to all-to-all style collectives under pjit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..launch.context import shard_hint
from .layers import COMPUTE_DTYPE, act_fn, dense_init

# Dispatch position computation:
#  "cumsum": one-hot cumsum — O(T·K·E) int32 intermediate (baseline; this is
#            what blew jamba/deepseek-v3 training memory, §Perf iteration 1)
#  "sort":   argsort + searchsorted rank-in-expert — O(T·K) memory
_DISPATCH_MODE = "sort"


def set_dispatch_mode(mode: str):
    global _DISPATCH_MODE
    assert mode in ("sort", "cumsum")
    _DISPATCH_MODE = mode

def moe_init(key, d: int, d_ff: int, n_experts: int, gated: bool,
             n_shared: int = 0, shared_d_ff: int = 0):
    ks = jax.random.split(key, 5)
    def stack(k, din, dout):
        return jax.random.normal(k, (n_experts, din, dout), jnp.float32) \
            * (1.0 / jnp.sqrt(din))
    p = {"router": dense_init(ks[0], d, n_experts),
         "w_up": stack(ks[1], d, d_ff),
         "w_down": stack(ks[2], d_ff, d)}
    if gated:
        p["w_gate"] = stack(ks[3], d, d_ff)
    if n_shared:
        from .layers import mlp_init
        p["shared"] = mlp_init(ks[4], d, shared_d_ff or d_ff * n_shared, gated)
    return p


# "dense": single-program scatter/gather dispatch (pjit decides layout;
#          GSPMD's scatter fallback replicates operands — §Perf iteration)
# "shardmap": explicit DP×TP token split + all-to-all expert exchange
#          (DeepSpeed-MoE-style, TPU-native; memory O(T_local·d) per chip)
_MOE_IMPL = "dense"


def set_moe_impl(impl: str):
    global _MOE_IMPL
    assert impl in ("dense", "shardmap")
    _MOE_IMPL = impl


def moe_ffn(p, x, *, top_k: int, act: str, gated: bool,
            capacity_factor: float = 1.25):
    """x: (B, S, d) -> (B, S, d).  Top-k routing with per-expert capacity.

    Serving note: capacity is computed over the call's token count, so
    prefill (per-batch) and decode (per-step) exhibit different drop
    behaviour — the standard MoE train/serve inconsistency; no-drop serving
    uses capacity_factor >= E/top_k.
    """
    from ..launch.context import current_plan
    plan = current_plan()
    if _MOE_IMPL == "shardmap" and plan is not None:
        y = _moe_ffn_shardmap(p, x, top_k=top_k, act=act, gated=gated,
                              capacity_factor=capacity_factor, plan=plan)
        if "shared" in p:
            from .layers import mlp
            y = y + mlp(p["shared"], x, act, gated)
        return y
    return _moe_ffn_dense(p, x, top_k=top_k, act=act, gated=gated,
                          capacity_factor=capacity_factor)


def _expert_compute(p, buf, act: str, gated: bool):
    """buf: (E, C, d) -> (E, C, d) through the expert FFNs."""
    up = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(COMPUTE_DTYPE))
    if gated:
        g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(COMPUTE_DTYPE))
        h = act_fn(act)(g.astype(jnp.float32)).astype(COMPUTE_DTYPE) * up
    else:
        h = act_fn(act)(up.astype(jnp.float32)).astype(COMPUTE_DTYPE)
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(COMPUTE_DTYPE))


def _local_dispatch(xt, router, top_k: int, capacity: int):
    """Per-shard routing: returns (buf (E,C,d), idx_e, idx_c, keep, gates)."""
    t, d = xt.shape
    e = router.shape[-1]
    logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)
    flat_e = gate_idx.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    rank_sorted = jnp.arange(t * top_k, dtype=jnp.int32) \
        - first.astype(jnp.int32)
    pos = jnp.zeros((t * top_k,), jnp.int32).at[order].set(rank_sorted)
    keep = pos < capacity
    buf = jnp.zeros((e, capacity, d), COMPUTE_DTYPE)
    idx_c = jnp.where(keep, pos, capacity - 1)
    src = jnp.where(keep[:, None],
                    jnp.repeat(xt.astype(COMPUTE_DTYPE), top_k, axis=0), 0)
    buf = buf.at[flat_e, idx_c].add(src)
    return buf, flat_e, idx_c, keep, gate_vals


def _moe_ffn_shardmap(p, x, *, top_k: int, act: str, gated: bool,
                      capacity_factor: float, plan):
    """Expert parallelism with explicit all-to-all (the §Perf fix for the
    GSPMD scatter-replication blowup).

    Tokens are split DP×TP (batch over "data", seq over "model"), each chip
    routes its local tokens into per-expert send buffers, a single
    all-to-all over "model" delivers them to the expert owners, experts run
    locally, and the reverse all-to-all + local gather combines.  Per-chip
    memory is O(T_local·K·d) — no global (E,C,d) buffer exists anywhere.
    """
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    e = p["router"].shape[-1]
    mesh = plan.mesh
    model_n = plan.model_size
    assert e % model_n == 0, (e, model_n)
    e_loc = e // model_n

    batch_ax = plan.batch_spec_axes(b)
    b_shards = 1
    if batch_ax is not None:
        axes = (batch_ax,) if isinstance(batch_ax, str) else batch_ax
        for a in axes:
            b_shards *= mesh.shape[a]
    seq_ax = "model" if s % model_n == 0 and s >= model_n else None
    s_shards = model_n if seq_ax else 1
    t_loc = (b // b_shards) * (s // s_shards)
    capacity = max(1, int(capacity_factor * t_loc * top_k / e))

    def body(xl, router, w_up, w_gate, w_down):
        # xl: (b_loc, s_loc, d) local tokens on this chip
        bl, sl, _ = xl.shape
        xt = xl.reshape(bl * sl, d)
        buf, flat_e, idx_c, keep, gate_vals = _local_dispatch(
            xt, router, top_k, capacity)
        # send: expert id j*e_loc+k lives on model-column j (tiled a2a:
        # axis0 splits into model_n contiguous expert groups; each peer's
        # C-slice concatenates along axis1)
        recv = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1,
                                  tiled=True)     # (e_loc, model_n·C, d)
        out = _expert_compute(
            {"w_up": w_up, "w_down": w_down, **({"w_gate": w_gate}
                                                if gated else {})},
            recv, act, gated)
        back = jax.lax.all_to_all(out, "model", split_axis=1, concat_axis=0,
                                  tiled=True)      # (E, C, d), owner view
        gathered = back[flat_e, idx_c]
        gathered = jnp.where(keep[:, None], gathered, 0)
        w = gate_vals.reshape(-1, 1).astype(jnp.float32)
        y = (gathered.astype(jnp.float32) * w).reshape(bl * sl, top_k, d)
        return y.sum(axis=1).astype(COMPUTE_DTYPE).reshape(bl, sl, d)

    x_spec = P(batch_ax, seq_ax, None)
    w_spec = P("model", None, None)
    body_sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec,
                  w_spec if gated else P(), w_spec),
        out_specs=x_spec, check_vma=False)
    return body_sm(x, p["router"], p["w_up"],
                   p["w_gate"] if gated else jnp.zeros((), COMPUTE_DTYPE),
                   p["w_down"])


def _moe_ffn_dense(p, x, *, top_k: int, act: str, gated: bool,
                   capacity_factor: float = 1.25):
    b, s, d = x.shape
    e = p["router"].shape[-1]
    t = b * s
    xt = x.reshape(t, d)

    logits = (xt.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                   # (T, E)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)         # (T, K)
    gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)

    capacity = max(1, int(capacity_factor * t * top_k / e))

    # position of each (token, k) within its expert's buffer
    if _DISPATCH_MODE == "sort":
        # O(T·K): stable-sort slots by expert id; rank within expert =
        # slot index − first index of that expert (searchsorted on the
        # sorted ids); scatter ranks back to slot order.
        flat_e = gate_idx.reshape(-1)                         # (T*K,)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        first = jnp.searchsorted(sorted_e, sorted_e, side="left")
        rank_sorted = jnp.arange(t * top_k, dtype=jnp.int32) \
            - first.astype(jnp.int32)
        pos = jnp.zeros((t * top_k,), jnp.int32).at[order].set(rank_sorted)
        pos = pos.reshape(t, top_k)
    else:
        onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)  # (T, K, E)
        flat = onehot.reshape(t * top_k, e)
        pos_in_expert = (jnp.cumsum(flat, axis=0) - flat)      # (T*K, E)
        pos = (pos_in_expert * flat).sum(-1).reshape(t, top_k)
    keep = pos < capacity                                     # drop overflow

    # scatter tokens into (E, C, d); hints keep the buffer EP-sharded and
    # the token-side tensors DP-sharded instead of replicated
    buf = jnp.zeros((e, capacity, d), COMPUTE_DTYPE)
    buf = shard_hint(buf, "model", None, None)
    idx_e = gate_idx.reshape(-1)
    idx_c = jnp.where(keep, pos, capacity - 1).reshape(-1)
    src = jnp.repeat(xt.astype(COMPUTE_DTYPE), top_k, axis=0)
    src = jnp.where(keep.reshape(-1, 1), src, 0)
    src = shard_hint(src, "batch", None)
    buf = buf.at[idx_e, idx_c].add(src)
    buf = shard_hint(buf, "model", None, None)

    # expert computation, E sharded over "model"
    up = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(COMPUTE_DTYPE))
    if gated:
        g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(COMPUTE_DTYPE))
        h = act_fn(act)(g.astype(jnp.float32)).astype(COMPUTE_DTYPE) * up
    else:
        h = act_fn(act)(up.astype(jnp.float32)).astype(COMPUTE_DTYPE)
    out_e = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(COMPUTE_DTYPE))

    # gather back + weighted combine
    gathered = out_e[idx_e, idx_c]                            # (T*K, d)
    gathered = jnp.where(keep.reshape(-1, 1), gathered, 0)
    weighted = gathered.astype(jnp.float32) \
        * gate_vals.reshape(-1, 1).astype(jnp.float32)
    out = weighted.reshape(t, top_k, d).sum(axis=1)

    y = out.reshape(b, s, d).astype(COMPUTE_DTYPE)
    if "shared" in p:
        from .layers import mlp
        y = y + mlp(p["shared"], x, act, gated)
    return y
