"""EngineSpec registry: the pluggable refine-engine surface.

The serving stack used to thread ``"pyen"``/``"dense_bf"`` string
switches through ``dist.cluster``, ``dist.scheduler`` and
``launch.serve``; every new engine meant touching all three.  An
:class:`EngineSpec` instead packages everything a ``dist.cluster.Worker``
needs to run one engine — whether it packs a dense slab, the
:class:`~repro.engine.backend.SolverBackend` that executes (and whose
:class:`~repro.engine.layout.SlabLayout` owns all slab geometry: lane
alignment, J buckets, hot-row packing), how to solve a batch of
cache-miss refine tasks, and how to build a device-mesh solver — and
the registry maps names to specs.  ``repro.service`` re-exports this
module as the public way to plug in an engine; the builtin specs are
``pyen`` (host Yen), ``dense_bf`` (jnp grouped BF) and ``pallas_bf``
(the fused Pallas kernel: compiled on a TPU, interpreted on the CPU).

A spec's ``refine(worker, misses, k, epoch)`` receives the worker (slab,
row_of, dtlp access), the cache-miss task list ``[(gid, a, b)]`` with
global vertex ids, and the serving epoch, and returns ``{(gid, a, b):
[(dist, global-path)]}`` for exactly those tasks — epoch checks and
cache fills stay in ``Worker.execute``, so an engine can never serve
stale weights by accident.  The epoch matters under streaming updates:
a worker double-buffers the previous epoch's slab/weights across one
commit (``Worker.slab_for`` / ``Worker.weights_for``), so an engine must
read THOSE accessors rather than ``worker.slab`` / ``dtlp.graph.w``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

from .backend import JnpBackend, PallasBackend, SolverBackend
from .layout import JNP_LAYOUT, SlabLayout

__all__ = [
    "EngineSpec",
    "register_engine",
    "get_engine",
    "available_engines",
]


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """Everything the worker runtime needs to run one refine engine.

    ``refine(worker, misses, k, epoch) -> {(gid, a, b): [(d, path)]}``
    solves a batch of partial-KSP tasks against the weights of
    ``epoch``; ``packs_slab`` makes each worker pack its
    owned subgraphs into a dense ``[S, z, z]`` slab at init, with all
    geometry (lane alignment, bucket shapes) coming from ``backend
    .layout``; ``make_mesh_solver(mesh, mesh_axis) -> (solver,
    s_multiple)`` is optional device-mesh wiring (None = the engine has
    no mesh path).
    """

    name: str
    refine: Callable
    # generator form of ``refine``: same signature, but yields once per
    # device round with that round's solve dispatched-but-unforced, and
    # returns the result dict as its StopIteration value.  The pipelined
    # scheduler steps these generators round-robin so one worker's device
    # solve overlaps another's host splicing; None = host-only engine
    # with no device rounds to overlap (the worker completes the future
    # synchronously).
    refine_async: Callable | None = None
    packs_slab: bool = False
    backend: SolverBackend | None = None
    make_mesh_solver: Callable | None = None
    # reference-path stream KSP-DG's filter phase consumes when this
    # engine serves a query (``repro.core.refstream`` registry name).
    # "lazy" — the Eppstein-style deviation-walk stream — is the serving
    # default: it removes the corridor-ties truncation mode and makes
    # each reference O(log) instead of one Yen round; "yen" remains
    # selectable as the simple-path fallback.
    ref_stream: str = "lazy"
    description: str = ""

    @property
    def layout(self) -> SlabLayout:
        """The slab geometry this engine's workers pack and solve in."""
        return self.backend.layout if self.backend is not None else JNP_LAYOUT

    @property
    def lane(self) -> int:
        """z-alignment of packed slabs (compat alias for layout.lane)."""
        return self.layout.lane

    @property
    def supports_mesh(self) -> bool:
        """Whether the spec carries device-mesh (``shard_map``) wiring."""
        return self.make_mesh_solver is not None


_REGISTRY: dict[str, EngineSpec] = {}


def register_engine(spec: EngineSpec, *, overwrite: bool = False) -> EngineSpec:
    """Register ``spec`` under ``spec.name``; returns it for chaining."""
    from repro.core.refstream import get_ref_stream

    if not overwrite and spec.name in _REGISTRY:
        raise ValueError(f"engine {spec.name!r} is already registered")
    get_ref_stream(spec.ref_stream)  # fail fast on unknown streams
    _REGISTRY[spec.name] = spec
    return spec


def get_engine(name) -> EngineSpec:
    """Resolve an engine name (or pass an :class:`EngineSpec` through).

    >>> get_engine("pyen").name
    'pyen'
    >>> get_engine("dense_bf").supports_mesh
    True
    """
    if isinstance(name, EngineSpec):
        return name
    spec = _REGISTRY.get(name)
    if spec is None:
        raise ValueError(
            f"unknown engine {name!r}; available: {available_engines()}"
        )
    return spec


def available_engines() -> list[str]:
    """Sorted names of every registered engine.

    >>> set(available_engines()) >= {"pyen", "dense_bf", "pallas_bf"}
    True
    """
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# builtin engines
# ---------------------------------------------------------------------------
def _pyen_refine(worker, misses, k, epoch):
    """Host Yen per pair on the epoch's subgraph view (QueryBolt-side)."""
    from repro.core.sssp import subgraph_view
    from repro.core.yen import ksp

    dtlp = worker.dtlp
    w = worker.weights_for(epoch)
    out = {}
    for gid, a, b in misses:
        sg = dtlp.partition.subgraphs[gid]
        view = subgraph_view(sg, w)
        local = ksp(
            view, sg.g2l[a], sg.g2l[b], k,
            mode="pyen", directed=dtlp.graph.directed,
        )
        out[(gid, a, b)] = [
            (d, tuple(int(sg.vertices[v]) for v in p)) for d, p in local
        ]
    return out


def _grouped_refine_async(worker, misses, k, epoch):
    """Generator form of :func:`_grouped_refine`: all misses through ONE
    grouped [S, J, z] lockstep-Yen slab solve, yielding once per device
    round with the round dispatched but not yet forced (the pipelined
    scheduler interleaves other workers' host work into those gaps).
    Returns the ``{(gid, a, b): [(d, path)]}`` dict.

    The slab is looked up BY EPOCH, never as ``worker.slab``: the body
    only runs at the first ``next()``, which under the pipelined
    scheduler may land after a streaming swap commits — by then
    ``worker.slab`` already holds the next epoch's weights and this
    batch's epoch lives in ``worker.prev_slab``."""
    from repro.dist.grouped_yen import grouped_ksp_async
    from repro.engine.dense import gather_slab_rows

    dtlp = worker.dtlp
    slab = worker.slab_for(epoch)
    gk_tasks = []
    for gid, a, b in misses:
        sg = dtlp.partition.subgraphs[gid]
        gk_tasks.append((worker.row_of[gid], sg.g2l[a], sg.g2l[b]))
    worker.stats.batches += 1
    # device-resident slab: per-round adjacency comes from an on-device
    # row gather against the persistent mirror instead of a host re-pack
    # + transfer (the steady-state query path never re-stages the slab)
    gather = None
    if slab.adj_dev is not None:
        gather = lambda rows: gather_slab_rows(slab, rows)  # noqa: E731
    results = yield from grouped_ksp_async(
        slab.adj, gk_tasks, k,
        solver=worker.solver, s_multiple=worker.s_multiple,
        backend=worker.spec.backend, gather=gather,
    )
    out = {}
    for (gid, a, b), local in zip(misses, results):
        sg = dtlp.partition.subgraphs[gid]
        out[(gid, a, b)] = [
            (float(d), tuple(int(sg.vertices[v]) for v in p))
            for d, p in local
        ]
    return out


def _grouped_refine(worker, misses, k, epoch):
    """Synchronous driver over :func:`_grouped_refine_async`, executed by
    the spec's :class:`SolverBackend` (jnp or Pallas) — or by the
    worker's mesh solver override when one is wired."""
    gen = _grouped_refine_async(worker, misses, k, epoch)
    while True:
        try:
            next(gen)
        except StopIteration as fin:
            return fin.value


def mesh_axis_names(mesh_axis) -> list:
    """Normalize a mesh-axis spec (one name or a sequence) to a list.

    >>> mesh_axis_names("data")
    ['data']
    >>> mesh_axis_names(("data", "model"))
    ['data', 'model']
    """
    return [mesh_axis] if isinstance(mesh_axis, str) else list(mesh_axis)


def _grouped_mesh_solver(backend):
    """``make_mesh_solver`` for any slab backend: the shard_map grouped-BF
    fixed point over a device mesh, with this backend's relaxation body
    (``mesh_relax``) inside the loop."""

    def make(mesh, mesh_axis):
        import numpy as np

        from repro import obs
        from repro.dist.shard_refine import make_refine_fn

        refine = make_refine_fn(mesh, axis=mesh_axis, backend=backend)
        names = mesh_axis_names(mesh_axis)
        s_multiple = int(np.prod([mesh.shape[a] for a in names]))
        desc = "x".join(str(int(mesh.shape[a])) for a in names)

        def solver(adj, init, bv, so, bn, cap):
            # same async-dispatch contract (and span) as
            # backend.solve_grouped, plus the mesh= shard-dispatch attr
            S, J, z = init.shape
            t0 = obs.clock()
            out = refine(adj, init, bv, so, bn, cap)
            obs.span_at("solve_grouped", t0, obs.clock() - t0,
                        backend=backend.name, S=S, J=J, z=z, mesh=desc)
            return out

        return solver, s_multiple

    return make


register_engine(EngineSpec(
    name="pyen",
    refine=_pyen_refine,
    packs_slab=False,
    description="host core.yen per pair through the shared PartialKSPCache",
))

# JnpBackend layout packs at lane=8: the jnp grouped solvers want a
# tight z (relaxation compute is O(z²)/problem)
_JNP_BACKEND = JnpBackend()
register_engine(EngineSpec(
    name="dense_bf",
    refine=_grouped_refine,
    refine_async=_grouped_refine_async,
    packs_slab=True,
    backend=_JNP_BACKEND,
    make_mesh_solver=_grouped_mesh_solver(_JNP_BACKEND),
    description="grouped [S, J, z] dense Bellman–Ford over per-worker slabs",
))

# PallasBackend layout packs at lane=128 with sublane-aligned,
# VMEM-bounded J buckets; on the CPU the kernel runs interpret=True (any
# other non-TPU platform raises) and produces byte-identical paths to
# dense_bf
_PALLAS_BACKEND = PallasBackend()
register_engine(EngineSpec(
    name="pallas_bf",
    refine=_grouped_refine,
    refine_async=_grouped_refine_async,
    packs_slab=True,
    backend=_PALLAS_BACKEND,
    make_mesh_solver=_grouped_mesh_solver(_PALLAS_BACKEND),
    description="fused Pallas bf_relax fixed point over 128-lane slabs "
                "(interpreted on the CPU)",
))
