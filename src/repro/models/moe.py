"""Mixture-of-Experts with expert parallelism over the ``model`` mesh axis.

Two execution paths, one routing semantics (top-k token choice, softmax
combine over chosen experts, deterministic capacity drop):

* ``train/prefill`` — tokens are split over BOTH mesh axes; a two-step
  shard_map all_to_all ships capacity-bounded buckets to the expert shards
  (GShard-style), local grouped matmuls run the E_local experts, and a
  second all_to_all returns outputs. This is what puts real all-to-all
  bytes on the roofline (DESIGN.md §5 EP).
* ``decode`` — few tokens: every model shard sees all tokens, computes its
  local experts' contribution for tokens routed there, and a psum combines.
  Dropless by construction.

Routing gradients: indices are stop-gradient; grads flow through the
softmax combine weights (standard token-choice MoE).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import CDT


@dataclasses.dataclass(frozen=True)
class MoEDims:
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25


def _route(x, w_router, dims: MoEDims):
    """Returns (expert ids (T,k), combine weights (T,k)) — fp32 softmax."""
    logits = jnp.einsum("td,de->te", x, w_router.astype(x.dtype))
    logits = logits.astype(jnp.float32)
    top_logits, top_ids = jax.lax.top_k(logits, dims.top_k)
    weights = jax.nn.softmax(top_logits, axis=-1)
    return jax.lax.stop_gradient(top_ids), weights


def _grouped_ffn(xe, w1, w3, w2):
    """xe: (E_loc, C, d); per-expert SwiGLU via grouped einsum."""
    g = jnp.einsum("ecd,edf->ecf", xe, w1)
    u = jnp.einsum("ecd,edf->ecf", xe, w3)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xe.dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, w2)


def _fill_buckets(x, dest, n_buckets: int, cap: int, fill_value=0):
    """Scatter rows of x (T, d) into (n_buckets, cap, d) by ``dest`` (T,),
    deterministic first-come order. Overflow and dest<0 rows are dropped.
    Also returns the (bucket, slot) of each row (-1 if dropped)."""
    T = dest.shape[0]
    destx = jnp.where(dest < 0, n_buckets, dest)    # park invalid at the end
    order = jnp.argsort(destx)                      # stable: groups buckets
    sd = destx[order]
    # slot within bucket = rank within its group
    start = jnp.searchsorted(sd, jnp.arange(n_buckets), side="left")
    slot_sorted = jnp.arange(T) - start[jnp.clip(sd, 0, n_buckets - 1)]
    keep = (slot_sorted < cap) & (sd < n_buckets)
    buckets = jnp.full((n_buckets, cap) + x.shape[1:], fill_value, x.dtype)
    # dropped rows get out-of-bounds targets; mode="drop" discards them
    safe_b = jnp.where(keep, sd, n_buckets)
    safe_s = jnp.where(keep, slot_sorted, cap)
    buckets = buckets.at[safe_b, safe_s].set(x[order], mode="drop")
    # map back: row -> (bucket, slot)
    inv = jnp.argsort(order)
    row_bucket = jnp.where(keep, sd, -1)[inv]
    row_slot = jnp.where(keep, slot_sorted, -1)[inv]
    return buckets, row_bucket, row_slot


def moe_ffn(x, params, dims: MoEDims, mesh, model_axis: str = "model",
            data_axes=("data",), mode: str = "train"):
    """x: (B, S, d) sharded P(data_axes, None, None). Returns same shape.

    params: {"router": (d, E), "w1": (E, d, f), "w3": (E, d, f),
             "w2": (E, f, d)} — expert dim sharded over ``model_axis``.
    """
    B, S, d = x.shape
    n_model = mesh.shape[model_axis]
    n_data = 1
    for a in data_axes:
        n_data *= mesh.shape[a]
    # a2a path needs a clean (batch over data) x (sequence over model) split
    if mode == "decode" or S % n_model or B % n_data:
        return _moe_replicated(x, params, dims, mesh, model_axis, data_axes)
    return _moe_a2a(x, params, dims, mesh, model_axis, data_axes)


def _moe_a2a(x, params, dims, mesh, model_axis, data_axes):
    """Input arrives SEQUENCE-SHARDED over the model axis (in_specs below):
    each device owns exactly its token slice, so the backward cotangent stays
    sharded instead of becoming a psum of mostly-zero f32 activations over
    the model axis (measured 1.75 GB x several per layer on kimi train_4k —
    see EXPERIMENTS.md §Perf iteration 1). The caller re-gathers the bf16
    output with one all-gather via its sharding constraint."""
    B, S, d = x.shape
    E = dims.n_experts
    n_model = mesh.shape[model_axis]
    E_loc = E // n_model
    in_spec = P(data_axes, model_axis, None)   # seq-sharded token slice

    def local(xb, w_router, w1, w3, w2):
        # xb: (B_loc, S/n_model, d) — exactly this shard's tokens
        shard = jax.lax.axis_index(model_axis)
        xt = xb.reshape(-1, d)
        T_loc = xt.shape[0]
        top_ids, weights = _route(xt, w_router, dims)           # (T_loc, k)
        k = dims.top_k
        # --- step 1: bucket by destination expert-shard, a2a over model ----
        flat_x = jnp.repeat(xt, k, axis=0)                      # (T_loc*k, d)
        flat_e = top_ids.reshape(-1)                            # global expert
        dest_shard = flat_e // E_loc
        cap_s = int((T_loc * k // n_model) * dims.capacity_factor) + 1
        bx, rb, rs = _fill_buckets(flat_x, dest_shard, n_model, cap_s)
        be, _, _ = _fill_buckets(flat_e[:, None], dest_shard, n_model, cap_s,
                                 fill_value=-1)
        recv_x = jax.lax.all_to_all(bx, model_axis, 0, 0, tiled=False)
        recv_e = jax.lax.all_to_all(be, model_axis, 0, 0, tiled=False)
        # recv_x: (n_model src, cap_s, d); local expert id in [0, E_loc)
        rx = recv_x.reshape(-1, d)
        re = recv_e.reshape(-1) - shard * E_loc   # empty slots stay < 0 -> dropped
        # --- step 2: regroup by local expert, grouped FFN ------------------
        cap_e = int(rx.shape[0] // E_loc * dims.capacity_factor) + 1
        ex, eb, es = _fill_buckets(rx, re, E_loc, cap_e)
        ey = _grouped_ffn(ex, w1, w3, w2)                       # (E_loc, cap_e, d)
        # gather back to received-row order, then a2a home
        valid = eb >= 0
        ry = jnp.where(valid[:, None],
                       ey[jnp.maximum(eb, 0), jnp.maximum(es, 0)], 0)
        ry = ry.reshape(n_model, cap_s, d)
        back = jax.lax.all_to_all(ry, model_axis, 0, 0, tiled=False)
        # back: (n_model dst-major, cap_s, d) rows in original bucket layout
        rowv = rb >= 0
        y_flat = jnp.where(rowv[:, None],
                           back[jnp.maximum(rb, 0), jnp.maximum(rs, 0)], 0)
        y = (y_flat.reshape(T_loc, k, d).astype(jnp.float32)
             * weights[..., None]).sum(axis=1).astype(xb.dtype)
        return y.reshape(xb.shape)

    x_sh = jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, in_spec))
    y = jax.shard_map(
        local, mesh=mesh,
        in_specs=(in_spec, P(), P(model_axis, None, None),
                  P(model_axis, None, None), P(model_axis, None, None)),
        out_specs=in_spec,
        check_vma=False,
    )(x_sh, params["router"], params["w1"], params["w3"], params["w2"])
    # one bf16 all-gather back to the residual-stream layout
    return jax.lax.with_sharding_constraint(
        y, jax.sharding.NamedSharding(mesh, P(data_axes, None, None)))


def _moe_replicated(x, params, dims, mesh, model_axis, data_axes):
    """Decode/small-batch path: tokens replicated over model axis; each shard
    computes its E_loc experts densely-masked; psum combines. Dropless."""
    B, S, d = x.shape
    E = dims.n_experts
    n_model = mesh.shape[model_axis]
    E_loc = E // n_model
    data_spec = P(data_axes, None, None)

    def local(xb, w_router, w1, w3, w2):
        shard = jax.lax.axis_index(model_axis)
        xt = xb.reshape(-1, d)                                   # (T, d)
        top_ids, weights = _route(xt, w_router, dims)            # (T, k)
        local_ids = top_ids - shard * E_loc
        in_range = (local_ids >= 0) & (local_ids < E_loc)
        w_masked = jnp.where(in_range, weights, 0.0)             # (T, k)
        # one-hot dispatch: T small in decode, so (T, k, E_loc) is cheap
        oh = jax.nn.one_hot(jnp.clip(local_ids, 0, E_loc - 1), E_loc,
                            dtype=xt.dtype) * in_range[..., None]
        xe = jnp.einsum("td,tke->etd", xt, oh)
        # (E_loc, T, d) -> grouped ffn
        ye = _grouped_ffn(xe, w1, w3, w2)                        # (E_loc, T, d)
        y = jnp.einsum("etd,tke,tk->td", ye.astype(jnp.float32), oh.astype(jnp.float32),
                       w_masked)
        y = jax.lax.psum(y, model_axis)
        return y.reshape(xb.shape).astype(xb.dtype)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(data_spec, P(), P(model_axis, None, None),
                  P(model_axis, None, None), P(model_axis, None, None)),
        out_specs=data_spec,
        check_vma=False,
    )(x, params["router"], params["w1"], params["w3"], params["w2"])
