"""Activation sharding constraints.

XLA's sharding propagation loses the TP sharding at reshape boundaries
(e.g. the (B, S, H*hd) -> (B, S, H, hd) head split after a column-parallel
projection) and will happily replicate attention across the model axis —
16x the FLOPs and HBM (caught by the loop-aware roofline; see EXPERIMENTS.md
§Perf iteration 1). ``constrain`` pins activations where propagation is
known to drop the ball, and is a no-op outside a mesh context so single-
device smoke tests run unchanged.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def _current_mesh():
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        try:
            from jax.interpreters import pxla
            mesh = pxla.thread_resources.env.physical_mesh
            if not mesh.empty:
                return mesh
        except Exception:
            pass
    return None


def constrain(x, *axes):
    """constrain(x, ('pod','data'), None, 'model', None) — axis entries not
    present on the active mesh are dropped; no active mesh -> identity."""
    mesh = _current_mesh()
    if mesh is None:
        return x
    names = set(mesh.axis_names)

    def filt(a, dim):
        if a is None:
            return None
        if not isinstance(a, (tuple, list)):
            a = (a,)
        kept = tuple(x_ for x_ in a if x_ in names)
        if not kept:
            return None
        extent = 1
        for n in kept:
            extent *= mesh.shape[n]
        if dim < extent:          # e.g. batch=1 long-context: don't shard
            return None
        # uneven dims (phi3: 40 heads / 16-way model axis) are allowed — XLA
        # pads. Waste is bounded by (ceil(dim/extent)*extent)/dim and shows
        # up honestly in the roofline FLOPs.
        return kept if len(kept) > 1 else kept[0]

    spec = P(*[filt(a, d) for a, d in zip(axes, x.shape)])
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


DP = ("pod", "data")


def row_parallel_dense(w, x, *, batch_axes=DP, tp_axis="model"):
    """Row-parallel (Megatron) matmul with the *textbook* communication
    schedule, bf16 on the wire (EXPERIMENTS.md §Perf iter 4c).

    XLA's default partitioning of this contraction all-reduces the f32 dot
    accumulator forward AND inserts x-sized f32 collectives in the backward
    (HLO audit). The custom VJP encodes what Megatron actually does:
      fwd:  y  = psum_tp(x_local @ w_local)              — ONE bf16 AR
      bwd:  dx = dy @ w_localᵀ                           — NO collective
            dw = psum_dp(x_localᵀ @ dy)                  — tiny (K_loc, N)
    with cotangents cast to the weight dtype. Falls back to a plain einsum
    when no mesh / no tp axis is active or the batch doesn't divide the DP
    extent (single-device tests, long_500k B=1)."""
    mesh = _current_mesh()
    if mesh is None or tp_axis not in mesh.axis_names:
        return jnp.einsum("...i,io->...o", x, w)
    names = set(mesh.axis_names)
    ba = tuple(a for a in batch_axes if a in names)
    extent = 1
    for a in ba:
        extent *= mesh.shape[a]
    if (x.shape[0] % max(extent, 1) != 0 or
            x.shape[-1] % mesh.shape[tp_axis] != 0):
        return jnp.einsum("...i,io->...o", x, w)
    return _row_parallel_custom(w, x, mesh, ba if ba else None, tp_axis,
                                x.ndim)


def _rp_specs(bspec, tp_axis, ndim):
    from jax.sharding import PartitionSpec as P
    x_spec = P(bspec, *([None] * (ndim - 2)), tp_axis)
    w_spec = P(tp_axis, None)
    y_spec = P(bspec, *([None] * (ndim - 1)))
    return x_spec, w_spec, y_spec


def _row_parallel_custom(w, x, mesh, bspec, tp_axis, ndim):
    x_spec, w_spec, y_spec = _rp_specs(bspec, tp_axis, ndim)

    @jax.custom_vjp
    def rp(w_, x_):
        def fwd_local(x_l, w_l):
            return jax.lax.psum(jnp.einsum("...i,io->...o", x_l, w_l), tp_axis)
        return jax.shard_map(fwd_local, mesh=mesh, in_specs=(x_spec, w_spec),
                             out_specs=y_spec, check_vma=False)(x_, w_)

    def rp_fwd(w_, x_):
        return rp(w_, x_), (w_, x_)

    def rp_bwd(res, dy):
        w_, x_ = res
        dy_c = dy.astype(w_.dtype)                   # bf16 on the wire

        def dx_local(dy_l, w_l):                     # no collective
            return jnp.einsum("...o,io->...i", dy_l, w_l)

        dx = jax.shard_map(dx_local, mesh=mesh, in_specs=(y_spec, w_spec),
                           out_specs=x_spec, check_vma=False)(dy_c, w_)

        dp_axes = bspec

        def dw_local(x_l, dy_l):                     # (K_loc, N) psum over DP
            dw_ = jnp.einsum("...i,...o->io", x_l, dy_l)
            return jax.lax.psum(dw_, dp_axes) if dp_axes else dw_

        dw = jax.shard_map(dw_local, mesh=mesh, in_specs=(x_spec, y_spec),
                           out_specs=w_spec, check_vma=False)(x_, dy_c)
        # cotangent dtypes MUST match the primal dtypes (custom_vjp contract;
        # the whisper encoder runs its residual stream in f32)
        return dw.astype(w_.dtype), dx.astype(x_.dtype)

    rp.defvjp(rp_fwd, rp_bwd)
    return rp(w, x)

