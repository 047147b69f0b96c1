"""Mixture-of-Experts layer with expert parallelism.

CAPre mapping (DESIGN.md section 2): the router's top-k choice is the
paper's *branch-dependent navigation* — which expert weights a token touches
is decided at run time.  CAPre's policy is to prefetch the union of branches;
here the full expert bank is the statically-known superset, staged
expert-parallel across the ``model`` mesh axis.

Two execution paths, same math:

  * ``moe_apply_dense`` — single-device / smoke-test path: capacity-based
    one-hot dispatch einsums (no collectives);
  * ``moe_apply_ep``    — shard_map path: activations arrive replicated over
    the ``model`` axis (the standard 2D layout for the attention TP blocks),
    so each model shard routes all of its data-shard's tokens but dispatches
    **only to its local expert slice** (E/n_model experts); the combine is a
    single psum over ``model``.  Dispatch-matmul cost per shard is
    T_local * E_local * C * d — 1/n_model of the dense path — and the only
    collective is the [T, d] psum (same volume as a Megatron MLP reduce).

An all-to-all token-exchange variant (tokens sharded over ``model`` too) is
a recorded hillclimb candidate in EXPERIMENTS.md §Perf.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def router_topk(x2d, router_w, n_experts: int, k: int, router_dtype=jnp.float32):
    """x2d [T, d] -> (probs [T, k], idx [T, k]) with softmax-renormalized
    top-k gates (qwen3/granite style: softmax over all experts, keep top-k)."""
    logits = x2d.astype(router_dtype) @ router_w.astype(router_dtype)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return top_p, top_i


def _dispatch_onehot(top_i, top_p, n_experts: int, capacity: int):
    """Build dispatch/combine tensors [T, E, C].

    Position within an expert's capacity buffer is the token's rank among
    tokens routed to that expert (overflow dropped).  Out-of-range expert
    indices (the EP path passes shifted local indices) one-hot to zero rows,
    which drops them for free."""
    T, k = top_i.shape
    oh = jax.nn.one_hot(top_i, n_experts, dtype=jnp.float32)  # [T, k, E]
    flat = oh.reshape(T * k, n_experts)
    ranks = (jnp.cumsum(flat, axis=0) - flat).reshape(T, k, n_experts)
    in_cap = ranks < capacity
    pos = jnp.where(in_cap, ranks, 0).astype(jnp.int32)
    pos_oh = jax.nn.one_hot(pos, capacity, dtype=jnp.float32) * in_cap[..., None] * oh[..., None]
    # pos_oh: [T, k, E, C]
    disp = pos_oh.sum(axis=1)
    comb = jnp.einsum("tkec,tk->tec", pos_oh, top_p.astype(jnp.float32))
    return disp, comb


def _expert_ffn(xe, we_gate, we_up, we_down, compute_dtype):
    """xe [E, C, d] -> [E, C, d] with per-expert gated MLP."""
    cast = lambda w: w.astype(compute_dtype)
    g = jnp.einsum("ecd,edf->ecf", xe, cast(we_gate))
    u = jnp.einsum("ecd,edf->ecf", xe, cast(we_up))
    h = jax.nn.silu(g) * u
    return jnp.einsum("ecf,efd->ecd", h, cast(we_down))


def _dispatch_scatter(x2, local_i, top_p, n_local: int, cap: int, compute_dtype):
    """Scatter-based dispatch (§Perf hillclimb variant): instead of the
    one-hot [T, E, C] matmuls (O(T^2) FLOPs via C ~ T), compute each
    (token, slot) rank with a cumsum over one-hot (cheap: no *d factor) and
    scatter rows directly into the [E*C, d] buffer; the combine gathers
    back.  Data movement O(T*k*d), no dispatch matmul."""
    T, k = local_i.shape
    oh = jax.nn.one_hot(local_i, n_local, dtype=jnp.float32)  # [T, k, E]
    flat = oh.reshape(T * k, n_local)
    ranks = (jnp.cumsum(flat, axis=0) - flat).reshape(T, k, n_local)
    rank = jnp.einsum("tke,tke->tk", ranks, oh).astype(jnp.int32)  # [T, k]
    valid = (local_i >= 0) & (local_i < n_local) & (rank < cap)
    slot = jnp.where(valid, local_i * cap + rank, n_local * cap)  # overflow row
    buf = jnp.zeros((n_local * cap + 1, x2.shape[1]), compute_dtype)
    xk = jnp.broadcast_to(x2[:, None, :], (T, k, x2.shape[1]))
    buf = buf.at[slot.reshape(-1)].set(xk.reshape(T * k, -1), mode="drop")
    return buf[:-1], slot, valid, rank


def _route_dispatch_ffn(x2, router_w, we_gate, we_up, we_down, cfg, compute_dtype,
                        expert_offset: int = 0, n_local: int = 0):
    """Shared core: route tokens in chunks, dispatch each chunk to the
    expert slice [expert_offset, expert_offset + n_local), run the expert
    FFN, combine.  Chunking bounds the [T, E, C] dispatch tensors (C scales
    with the chunk size).  Returns the (partial) output [T, d]."""
    E, k = cfg.n_experts, cfg.experts_per_token
    n_local = n_local or E
    T, d = x2.shape
    chunk = min(cfg.moe_chunk, T)
    while T % chunk:
        chunk //= 2
    n_chunks = T // chunk
    cap = max(1, int(cfg.capacity_factor * chunk * k / E))

    def one_chunk(xc):
        top_p, top_i = router_topk(xc, router_w, E, k)
        local_i = top_i - expert_offset  # out-of-slice -> out-of-range -> dropped
        if cfg.moe_dispatch == "scatter":
            xe_flat, slot, valid, _ = _dispatch_scatter(
                xc, local_i, top_p, n_local, cap, compute_dtype
            )
            xe = xe_flat.reshape(n_local, cap, d)
            ye = _expert_ffn(xe, we_gate, we_up, we_down, compute_dtype)
            ye_flat = ye.reshape(n_local * cap, d)
            gathered = jnp.take(ye_flat, jnp.where(valid, slot, 0), axis=0)  # [T,k,d]
            w = jnp.where(valid, top_p, 0.0).astype(compute_dtype)
            return jnp.einsum("tkd,tk->td", gathered, w)
        disp, comb = _dispatch_onehot(local_i, top_p, n_local, cap)
        xe = jnp.einsum("tec,td->ecd", disp.astype(compute_dtype), xc)
        ye = _expert_ffn(xe, we_gate, we_up, we_down, compute_dtype)
        return jnp.einsum("tec,ecd->td", comb.astype(compute_dtype), ye)

    if n_chunks == 1:
        return one_chunk(x2)
    xc = x2.reshape(n_chunks, chunk, d)
    yc = jax.lax.map(one_chunk, xc)
    return yc.reshape(T, d)


def moe_apply_dense(x, p, cfg, compute_dtype):
    """Single-shard reference path. x [B, S, d]."""
    B, S, d = x.shape
    x2 = x.reshape(B * S, d)
    y = _route_dispatch_ffn(
        x2, p["router"], p["we_gate"], p["we_up"], p["we_down"], cfg, compute_dtype
    )
    return y.reshape(B, S, d)


def moe_apply_ep(x, p, cfg, compute_dtype, mesh, data_axes, model_axis: str):
    """Expert-parallel path under shard_map (see module docstring)."""
    E = cfg.n_experts
    n_model = mesh.shape[model_axis]
    E_local = E // n_model

    def body(xl, router_w, we_gate, we_up, we_down):
        Bl, S, d = xl.shape
        x2 = xl.reshape(Bl * S, d)
        offset = jax.lax.axis_index(model_axis) * E_local
        y = _route_dispatch_ffn(
            x2, router_w, we_gate, we_up, we_down, cfg, compute_dtype,
            expert_offset=offset, n_local=E_local,
        )
        y = jax.lax.psum(y, model_axis)
        return y.reshape(Bl, S, d)

    dspec = P(data_axes, None, None)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            dspec,
            P(None, None),  # router replicated
            P(model_axis, None, None),  # expert banks sharded over model
            P(model_axis, None, None),
            P(model_axis, None, None),
        ),
        out_specs=dspec,
        check_vma=False,
    )(x, p["router"], p["we_gate"], p["we_up"], p["we_down"])


def moe_apply_fsdp(x, p, cfg, compute_dtype, mesh, batch_axes):
    """FSDP-local path: tokens never leave their device; the expert banks
    arrive via the shard_map replication gather (the per-layer FSDP weight
    all-gather) and every device runs the full dense dispatch on its local
    tokens — routing/dispatch math is entirely collective-free."""
    def body(xl, router_w, wg, wu, wd):
        Bl, S, d = xl.shape
        y = _route_dispatch_ffn(
            xl.reshape(Bl * S, d), router_w, wg, wu, wd, cfg, compute_dtype
        )
        return y.reshape(Bl, S, d)

    bspec = P(batch_axes, None, None)
    rep2, rep3 = P(None, None), P(None, None, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(bspec, rep2, rep3, rep3, rep3),
        out_specs=bspec, check_vma=False,
    )(x, p["router"], p["we_gate"], p["we_up"], p["we_down"])


def moe_apply_ep_a2a(x, p, cfg, compute_dtype, mesh, batch_axes, model_axis):
    """Switch/DeepSpeed-style expert parallelism: tokens sharded over every
    mesh axis; each device routes its own tokens (scatter dispatch, no
    dispatch matmul, no replication) and exchanges capacity buffers with the
    expert shards via all-to-all over ``model``.  Collective payload is the
    [E, C_local, d] activation buffer — independent of the expert bank size."""
    E, k = cfg.n_experts, cfg.experts_per_token
    n_model = mesh.shape[model_axis]
    E_local = E // n_model

    def body(xl, router_w, wg, wu, wd):
        Bl, S, d = xl.shape
        x2 = xl.reshape(Bl * S, d)
        T = x2.shape[0]
        cap = max(1, int(cfg.capacity_factor * T * k / E))
        top_p, top_i = router_topk(x2, router_w, E, k)
        buf, slot, valid, _ = _dispatch_scatter(x2, top_i, top_p, E, cap, compute_dtype)
        xe = buf.reshape(E, cap, d)
        # exchange: shard m receives every origin's buffers for its experts
        # (tiled all-to-all: expert-block dim scatters, capacity dim gathers)
        xe = jax.lax.all_to_all(xe, model_axis, split_axis=0, concat_axis=1, tiled=True)
        ye = _expert_ffn(xe, wg, wu, wd, compute_dtype)  # [E_local, n*cap, d]
        ye = jax.lax.all_to_all(ye, model_axis, split_axis=1, concat_axis=0, tiled=True)
        ye_flat = jnp.concatenate([ye.reshape(E * cap, d),
                                   jnp.zeros((1, d), compute_dtype)], axis=0)
        gathered = jnp.take(ye_flat, jnp.where(valid, slot, E * cap), axis=0)
        w = jnp.where(valid, top_p, 0.0).astype(compute_dtype)
        y = jnp.einsum("tkd,tk->td", gathered.reshape(T, k, d), w)
        return y.reshape(Bl, S, d)

    bspec = P(batch_axes, None, None)
    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(bspec, P(None, None), P(model_axis, None, None),
                  P(model_axis, None, None), P(model_axis, None, None)),
        out_specs=bspec, check_vma=False,
    )(x, p["router"], p["we_gate"], p["we_up"], p["we_down"])
    # under remat="dots_collectives" the saved name keeps the backward from
    # re-running the all-to-alls (collectives are the scarce resource)
    from jax.ad_checkpoint import checkpoint_name

    return checkpoint_name(out, "moe_out")


def moe_apply(x, p, cfg, compute_dtype, mesh_info=None):
    """Dispatch to the dense / EP-psum / fsdp-local / EP-a2a implementation."""
    if mesh_info is not None:
        mesh, data_axes, model_axis = mesh_info[:3]
        mode = mesh_info[3] if len(mesh_info) > 3 else "ep_psum"
        if model_axis is None:
            return moe_apply_fsdp(x, p, cfg, compute_dtype, mesh, data_axes)
        if mesh.shape[model_axis] > 1 and cfg.n_experts % mesh.shape[model_axis] == 0:
            if mode == "ep_a2a":
                return moe_apply_ep_a2a(
                    x, p, cfg, compute_dtype, mesh, data_axes, model_axis
                )
            return moe_apply_ep(x, p, cfg, compute_dtype, mesh, data_axes, model_axis)
    return moe_apply_dense(x, p, cfg, compute_dtype)
