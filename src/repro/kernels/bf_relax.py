"""Pallas TPU kernel: fused masked min-plus Bellman–Ford relaxation.

One grid step computes, for subgraph s and an output vertex tile t of
width TV:

    new[j, t] = clamp_cap( min( dist[j, t],
                  min_u  dist[j, u] + adj[u, t]  (spur-row cuts applied) ) )

Memory plan (TPU v5e, 16 MiB scoped VMEM limit per kernel):
    dist block    [J, z]    f32   J≤32, z≤1024  → ≤128 KiB
    adj block     [z, TV]   f32   z≤1024, TV=128 → 512 KiB
    The u-reduction is a ``fori_loop`` over 128-aligned chunks of z, so
    only one chunk's [J, UZ, TV] intermediate (≤ 2 MiB) is live at a
    time; a statically unrolled loop keeps every chunk live (a 30.46 MiB
    scoped allocation at J=32, z=1024 with UZ=256, over the limit).
    The least ``vmem_limit_bytes`` the v5e compiler
    accepts for this kernel (double-buffered blocks included): 0.63 MiB
    at (J, z) = (8, 128), 2.73 MiB at (32, 128), 1.59 MiB at (8, 1024),
    4.14 MiB at (32, 1024) — every bucket ``PALLAS_LAYOUT`` produces
    (J ≤ 32) fits the default limit, and tests/test_chip_compile.py
    compiles the largest.
    MXU is unused (tropical semiring has no matmul); this is a VPU
    min/add kernel and the roofline treats it as memory-bound, so tiles
    are chosen to stream adj exactly once per output tile.

Mosaic tiling: every block's last two dims are (8, 128)-aligned or the
full array dims, so ``cap`` travels as [S, J, 1]; the self tile of dist
is an aligned ``pl.ds`` ref slice (``dynamic_slice`` has no TPU
lowering inside a kernel).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

INF = 3.0e38  # python float: jnp constants become captured consts in Pallas

_TV = 128   # output vertex tile (lane dimension)
_UZ = 128   # u-reduction chunk


def _bf_relax_kernel(dist_ref, adj_ref, spur_ref, ban_ref, cap_ref, out_ref):
    # dist_ref [1, J, z]; adj_ref [1, z, TV]; spur_ref [1, J, z];
    # ban_ref [1, J, TV]; cap_ref [1, J, 1]; out_ref [1, J, TV]
    J, z = dist_ref.shape[1], dist_ref.shape[2]
    TV = out_ref.shape[2]
    ban = ban_ref[0]           # [J, TV] f32 0/1

    def chunk(c, best):
        u0 = pl.multiple_of(c * _UZ, _UZ)
        dc = dist_ref[0, :, pl.ds(u0, _UZ)]                     # [J, UZ]
        ac = adj_ref[0, pl.ds(u0, _UZ), :]                      # [UZ, TV]
        sc = spur_ref[0, :, pl.ds(u0, _UZ)]
        contrib = dc[:, :, None] + ac[None, :, :]               # [J, UZ, TV]
        cut = (sc[:, :, None] * ban[:, None, :]) > 0.5
        contrib = jnp.where(cut, INF, contrib)
        return jnp.minimum(best, jnp.min(contrib, axis=1))

    best = jax.lax.fori_loop(0, z // _UZ, chunk,
                             jnp.full((J, TV), INF, jnp.float32))

    # self tile of dist for the jnp.minimum(dist, ·) term
    t0 = pl.multiple_of(pl.program_id(1) * TV, TV)
    d_self = dist_ref[0, :, pl.ds(t0, TV)]
    new = jnp.minimum(d_self, best)
    new = jnp.where(new > cap_ref[0], INF, new)
    out_ref[0] = new


_SUB = 8    # f32 sublane tile (J alignment)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bf_relax(dist, adj, spur_onehot, banned_next, cap, *, interpret=False):
    """dist [S,J,z] f32, adj [S,z,z] f32, spur_onehot/banned_next [S,J,z]
    f32 0/1 masks, cap [S,J] f32 → relaxed dist [S,J,z].

    z and J need not be tile-aligned: the wrapper pads z up to the lane
    tile (INF-filled adj columns/rows and dist lanes — padded vertices
    are unreachable and never win a min) and J up to the f32 sublane
    tile (all-INF dist rows no-op through the relaxation), then slices
    the result back, so tight-lane jnp slabs drop in without repacking.
    """
    S, J, z = dist.shape
    z_pad = _TV * ((z + _TV - 1) // _TV)
    j_pad = _SUB * ((J + _SUB - 1) // _SUB)
    if z_pad != z or j_pad != J:
        dz, dj = z_pad - z, j_pad - J
        dist = jnp.pad(dist, ((0, 0), (0, dj), (0, dz)),
                       constant_values=INF)
        adj = jnp.pad(adj, ((0, 0), (0, dz), (0, dz)), constant_values=INF)
        spur_onehot = jnp.pad(spur_onehot, ((0, 0), (0, dj), (0, dz)))
        banned_next = jnp.pad(banned_next, ((0, 0), (0, dj), (0, dz)))
        cap = jnp.pad(cap, ((0, 0), (0, dj)), constant_values=INF)
    grid = (S, z_pad // _TV)
    out = pl.pallas_call(
        _bf_relax_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, j_pad, z_pad), lambda s, t: (s, 0, 0)),
            pl.BlockSpec((1, z_pad, _TV), lambda s, t: (s, 0, t)),
            pl.BlockSpec((1, j_pad, z_pad), lambda s, t: (s, 0, 0)),
            pl.BlockSpec((1, j_pad, _TV), lambda s, t: (s, 0, t)),
            pl.BlockSpec((1, j_pad, 1), lambda s, t: (s, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, j_pad, _TV), lambda s, t: (s, 0, t)),
        out_shape=jax.ShapeDtypeStruct((S, j_pad, z_pad), jnp.float32),
        interpret=interpret,
    )(dist, adj, spur_onehot, banned_next, cap[:, :, None])
    return out[:, :J, :z]
