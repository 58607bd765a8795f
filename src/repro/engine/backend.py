"""SolverBackend: the pluggable grouped-solve execution layer.

One backend = one way to run the hot loop — a converged masked grouped
Bellman–Ford over the owner-aligned [S, J, z] slab layout — plus the
:class:`~repro.engine.layout.SlabLayout` geometry that execution wants.
Engine dispatch used to mean "which jnp function"; it now means "which
backend object":

* :class:`JnpBackend` — the reference path: the shape-bucketed jitted
  ``bf_solve_grouped`` + ``bf_parents_grouped`` pair
  (``engine.yen_engine.grouped_solver``), tight lane=8 slabs.
* :class:`PallasBackend` — a fixed-point ``lax.while_loop`` over the
  fused ``kernels.bf_relax`` Pallas kernel (128-lane slabs, VMEM-
  bounded J buckets), with parents recovered post-convergence by the
  same ``bf_parents_grouped`` the jnp path uses.  The kernel runs
  compiled on a TPU and interpreted on the CPU (where the tests run);
  any other platform is an error (``kernels.ops.interpret_mode``).

Both backends implement the same contract —

    solve_grouped(adj, init, banned_v, spur_onehot, banned_next, cap)
        -> (dist [S, J, z], parents [S, J, z])

— the exact signature ``dist.grouped_yen._solve_round`` dispatches (and
that a ``shard_refine.make_refine_fn`` mesh solver overrides).  The two
relax the same candidate set with exact f32 min-plus arithmetic, so
their fixed points — and therefore every path served through them — are
byte-identical (asserted in tests/test_backend.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs

from .layout import JNP_LAYOUT, PALLAS_LAYOUT, SlabLayout

__all__ = ["SolverBackend", "JnpBackend", "PallasBackend"]


class SolverBackend:
    """Interface: grouped-solve execution + the slab geometry it wants."""

    name: str = "abstract"
    layout: SlabLayout
    # buffer donation for per-round scratch: None auto-enables off-CPU
    # (CPU jax ignores donation with a warning, so backends keep it off
    # there); True/False force.  Donated buffers MUST be fresh per round
    # — ``SlabLayout.pack_round`` is the only sanctioned producer.
    donate: bool | None = None

    @property
    def _donate(self) -> bool:
        if self.donate is None:
            return jax.default_backend() not in ("cpu",)
        return bool(self.donate)

    def solve_grouped(self, adj, init, banned_v, spur_onehot, banned_next,
                      cap):
        """Converged (dist [S,J,z], parents [S,J,z]) for one bucket.

        ``adj`` [S,z,z] min-plus slab; ``init`` [S,J,z] f32 (+INF except
        sources/warm starts); ``banned_v``/``spur_onehot``/
        ``banned_next`` [S,J,z] bool masks; ``cap`` [S,J] f32 distance
        caps (early termination).  All-INF padding rows must no-op.

        The call is ASYNC-DISPATCHED: it returns device arrays without
        blocking (no ``jax.block_until_ready``), so a pipelined caller
        can overlap the device solve with host-side splicing and only
        pay the wait when it forces the result to numpy.
        """
        raise NotImplementedError

    def mesh_relax(self):
        """``(prep, step)``: the building blocks a ``shard_map`` mesh
        fixed point (``repro.dist.shard_refine.make_refine_fn``) iterates
        per shard.

        ``prep(spur_onehot, banned_next)`` converts the bool masks once,
        outside the while_loop (the Pallas kernel wants f32 masks; the
        jnp path passes them through).  ``step(dist, adj, banned_v,
        so_p, bn_p, cap)`` is ONE full while-body iteration — the
        relaxation, the banned-vertex re-mask, and the cap clamp — in
        exactly the op order this backend's single-device
        ``solve_grouped`` uses.  BF relaxation is idempotent at its
        fixed point, so a mesh loop that runs extra iterations on an
        already-converged shard (while a psum-any says some OTHER shard
        still changes) lands on the same bytes as the single-device
        solve.
        """
        raise NotImplementedError

    def __repr__(self):  # pragma: no cover - debugging nicety
        return f"{type(self).__name__}(layout={self.layout.name!r})"


class JnpBackend(SolverBackend):
    """The jnp reference solver on tight lane=8 slabs."""

    name = "jnp"
    layout = JNP_LAYOUT

    def __init__(self, donate: bool | None = None):
        self.donate = donate

    def solve_grouped(self, adj, init, banned_v, spur_onehot, banned_next,
                      cap):
        from .yen_engine import grouped_solver

        S, J, z = init.shape
        t0 = obs.clock()
        out = grouped_solver(S, J, z, donate=self._donate)(
            adj, init, banned_v, spur_onehot, banned_next, cap
        )
        # dispatch cost only — the solve is async, the device keeps
        # cooking after this returns; the wait shows up in the caller's
        # "solve" (future.step) span when the result is forced
        obs.span_at("solve_grouped", t0, obs.clock() - t0,
                    backend=self.name, S=S, J=J, z=z)
        return out

    def mesh_relax(self):
        from .dense import INF, bf_step_grouped

        def prep(so, bn):
            return so, bn

        def step(dist, adj, bv, so, bn, cap):
            # mirrors bf_solve_grouped's body: relax → banned-vertex
            # re-mask → cap clamp, in that order
            new = bf_step_grouped(dist, adj, so, bn)
            new = jnp.where(bv, INF, new)
            return jnp.where(new > cap[:, :, None], INF, new)

        return prep, step


@functools.lru_cache(maxsize=None)
def _pallas_grouped_solver(S, J, z, interpret, donate=False):
    """Shape-bucketed jitted Pallas fixed-point (solve + parents).

    The while_loop iterates the fused ``bf_relax`` kernel — which
    applies the spur cut and the cap clamp in-kernel — re-masking
    ``banned_v`` between iterations (a banned vertex can be re-reached
    through relaxation, exactly as in ``bf_solve_grouped``).  The
    candidate sets and f32 arithmetic match the jnp path op-for-op, so
    convergence takes the same iteration count and lands on the same
    bytes; parents then come from the shared ``bf_parents_grouped``.
    """
    from repro.kernels.bf_relax import bf_relax

    from .dense import INF, bf_parents_grouped

    def run(adj, init, bv, so, bn, cap):
        so_f = so.astype(jnp.float32)
        bn_f = bn.astype(jnp.float32)
        dist0 = jnp.where(bv, INF, init)

        def cond(state):
            _, changed, it = state
            return changed & (it < z)

        def body(state):
            dist, _, it = state
            new = bf_relax(dist, adj, so_f, bn_f, cap, interpret=interpret)
            new = jnp.where(bv, INF, new)
            changed = jnp.any(new < dist)
            return new, changed, it + 1

        dist, _, _ = jax.lax.while_loop(
            cond, body, (dist0, jnp.bool_(True), jnp.int32(0))
        )
        parent = bf_parents_grouped(adj, dist, so, bn)
        return dist, parent

    if donate:
        # per-round scratch only: the fixed-point outputs reuse its
        # device memory instead of re-allocating
        from .yen_engine import DONATED_ARGS

        return jax.jit(run, donate_argnums=DONATED_ARGS)
    return jax.jit(run)


class PallasBackend(SolverBackend):
    """The Pallas ``bf_relax`` kernel iterated to its fixed point.

    ``interpret=None`` (default) follows the platform: compiled on a
    TPU, interpreted on the CPU, an error anywhere else
    (``kernels.ops.interpret_mode``).  Pass ``True``/``False`` to force
    either (tests force ``True`` for parity runs).
    """

    name = "pallas"
    layout = PALLAS_LAYOUT

    def __init__(self, interpret: bool | None = None,
                 donate: bool | None = None):
        self.interpret = interpret
        self.donate = donate

    @property
    def interpreted(self) -> bool:
        """Whether the kernel runs in interpret mode on this process."""
        if self.interpret is None:
            from repro.kernels.ops import interpret_mode

            return interpret_mode()
        return bool(self.interpret)

    def solve_grouped(self, adj, init, banned_v, spur_onehot, banned_next,
                      cap):
        S, J, z = init.shape
        t0 = obs.clock()
        out = _pallas_grouped_solver(
            S, J, z, self.interpreted, donate=self._donate
        )(adj, init, banned_v, spur_onehot, banned_next, cap)
        obs.span_at("solve_grouped", t0, obs.clock() - t0,
                    backend=self.name, S=S, J=J, z=z,
                    interpret=self.interpreted)
        return out

    def mesh_relax(self):
        from repro.kernels.bf_relax import bf_relax

        from .dense import INF

        interpret = self.interpreted

        def prep(so, bn):
            return so.astype(jnp.float32), bn.astype(jnp.float32)

        def step(dist, adj, bv, so_f, bn_f, cap):
            # mirrors _pallas_grouped_solver's body: bf_relax applies the
            # spur cut and cap clamp in-kernel, then the bv re-mask
            new = bf_relax(dist, adj, so_f, bn_f, cap, interpret=interpret)
            return jnp.where(bv, INF, new)

        return prep, step
