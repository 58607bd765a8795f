"""shard_map production paths over a device mesh.

Three factories, each returning a jitted function whose per-shard body
runs on the local block of the owner-aligned [S, ...] slab layout:

* ``make_refine_fn``    — grouped masked BF refine (solve + parents),
  subgraph rows sharded across the mesh.  The per-iteration relaxation
  is communication-free (problems are co-located with their subgraph's
  slab row), but the FIXED POINT is global: the convergence flag is a
  psum-any across shards, so every shard keeps stepping until the whole
  batch has converged.  Extra steps on an already-converged shard are
  bitwise no-ops (BF relaxation is idempotent at its fixed point), so
  the mesh solve is byte-identical to the single-device backends.
  The relaxation body comes from a
  :class:`repro.engine.backend.SolverBackend` (``mesh_relax``) — both
  the jnp ``bf_step_grouped`` path and the Pallas ``bf_relax`` kernel
  run under the same shard_map wrapper;
* ``make_update_fn``    — scatter of edge-weight updates into the
  sharded [S, z, z] adjacency slabs (padding rows marked -1 ignored);
* ``make_allreduce_fn`` — int8-quantized compressed all-reduce with an
  error-feedback residual (the gradient/statistics sync path).

Semantics are mesh-shape independent: a (1,1) mesh reproduces the
single-process engine bit-for-bit (tests), a 512-device layout shards S
and keeps the same per-shard program (dry-run cells).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.engine.dense import INF, bf_parents_grouped


def _shard_map(fn, mesh, in_specs, out_specs):
    # check_vma=False: a compiled pallas_call's out_shape carries no vma
    # (varying-manual-axes) type, which the checked mode refuses
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _axis_size(axis):
    """Total device count across ``axis`` (a name or tuple of names)."""
    names = (axis,) if isinstance(axis, str) else tuple(axis)
    size = 1
    for name in names:
        size = size * jax.lax.psum(1, name)
    return size


def _linear_index(axis):
    """Linearized shard index along ``axis`` (major-to-minor order)."""
    names = (axis,) if isinstance(axis, str) else tuple(axis)
    idx = jnp.int32(0)
    for name in names:
        idx = idx * jax.lax.psum(1, name) + jax.lax.axis_index(name)
    return idx


def make_refine_fn(mesh, axis=("data", "model"), max_iters: int | None = None,
                   backend=None):
    """(adj [S,z,z], dist0 [S,J,z], bv, so, bn [S,J,z], cap [S,J]) →
    (dist [S,J,z], parent [S,J,z]) with S sharded over ``axis``.

    ``backend`` supplies the per-iteration relaxation body via
    ``SolverBackend.mesh_relax`` (default: the jnp reference backend) —
    this is how BOTH ``dense_bf`` and ``pallas_bf`` get a mesh path from
    one wrapper.  Each relaxation step is purely local (problems were
    grouped next to their subgraph's slab row by the host dispatch);
    the only collective is the per-iteration psum-any on the
    convergence flag, which keeps every shard in the while_loop until
    the GLOBAL fixed point is reached.  Shards that converged early
    relax idempotently, so the result is byte-identical to the
    single-device ``solve_grouped`` of the same backend.
    """
    if backend is None:
        from repro.engine.backend import JnpBackend

        backend = JnpBackend()
    prep, step = backend.mesh_relax()
    spec = P(axis)

    def local(adj, dist0, bv, so, bn, cap):
        z = dist0.shape[-1]
        iters = z if max_iters is None else max_iters
        so_p, bn_p = prep(so, bn)
        dist0 = jnp.where(bv, INF, dist0)

        def cond(state):
            _, changed, it = state
            return changed & (it < iters)

        def body(state):
            dist, _, it = state
            new = step(dist, adj, bv, so_p, bn_p, cap)
            # psum-any: converged shards keep relaxing (idempotent)
            # until the slowest shard's problems reach the fixed point
            changed = jax.lax.psum(
                jnp.any(new < dist).astype(jnp.int32), axis) > 0
            return new, changed, it + 1

        dist, _, _ = jax.lax.while_loop(
            cond, body, (dist0, jnp.bool_(True), jnp.int32(0))
        )
        parent = bf_parents_grouped(adj, dist, so, bn)
        return dist, parent

    return jax.jit(_shard_map(local, mesh, (spec,) * 6, (spec, spec)))


def make_update_fn(mesh, axis=("data", "model")):
    """Scatter a weight-update batch into sharded adjacency slabs.

    Returns ``update(adj, slab_idx, uu, vv, ww) -> adj'`` where
    ``slab_idx[i]`` is the GLOBAL slab row of update i (-1 marks a
    padding entry and is ignored), ``uu/vv`` local vertex ids and ``ww``
    the new float32 weight.  The update arrays are replicated; every
    shard applies only the rows it owns — a scatter, not an all-to-all.

    Contract: ``ww[i]`` must be the EFFECTIVE slab value for cell
    (slab_idx, uu, vv) — i.e. the min over parallel edges between the
    pair, as ``dist.cluster.Worker._min_weight`` computes host-side —
    and a batch must not carry duplicate cells (plain ``.set`` scatter:
    duplicate-cell order is unspecified).  The host dispatch owns both.
    """
    spec = P(axis)
    rep = P()

    def local(adj, slab_idx, uu, vv, ww):
        s_loc = adj.shape[0]
        off = _linear_index(axis) * s_loc
        local_row = slab_idx - off
        valid = (slab_idx >= 0) & (local_row >= 0) & (local_row < s_loc)
        row = jnp.where(valid, local_row, s_loc)  # s_loc is OOB → dropped
        return adj.at[row, uu, vv].set(ww, mode="drop")

    return jax.jit(_shard_map(local, mesh, (spec, rep, rep, rep, rep), spec))


def make_allreduce_fn(mesh, compressed: bool = True, axis=("data", "model")):
    """Mean all-reduce of a per-device vector, optionally int8-compressed.

    Returns ``ar(x, resid) -> (avg, new_resid)``.  Compressed mode
    quantizes ``x + resid`` to int8 (symmetric, scale = max/127), reduces
    the dequantized values, and keeps the quantization error as the next
    call's error-feedback residual — unbiased over time, 4x less wire
    traffic.  Uncompressed mode is a plain psum-mean with zero residual.
    """
    rep = P()

    def local(x, resid):
        n = _axis_size(axis)
        if not compressed:
            avg = jax.lax.psum(x, axis) / n
            return avg, jnp.zeros_like(x)
        y = x + resid
        scale = jnp.maximum(jnp.max(jnp.abs(y)) / 127.0, 1e-30)
        q = jnp.clip(jnp.round(y / scale), -127, 127).astype(jnp.int8)
        deq = q.astype(jnp.float32) * scale
        avg = jax.lax.psum(deq, axis) / n
        return avg, y - deq

    return jax.jit(_shard_map(local, mesh, (rep, rep), (rep, rep)))
