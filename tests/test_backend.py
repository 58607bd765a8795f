"""SolverBackend layer: slab-layout geometry rules, jnp-vs-Pallas
grouped-solve parity (byte-identical, interpret mode on CPU), and the
end-to-end serving equivalence of the ``pallas_bf`` engine."""

import numpy as np
import pytest
from tests._hypothesis_compat import given, settings, st

import jax
import jax.numpy as jnp

from repro.core.dtlp import DTLP
from repro.data.roadnet import WeightUpdateStream, grid_road_network
from repro.engine.backend import JnpBackend, PallasBackend
from repro.engine.dense import INF, pack_subgraphs
from repro.engine.layout import JNP_LAYOUT, PALLAS_LAYOUT, SlabLayout
from repro.service import (
    KSPService,
    QueryRequest,
    ServiceConfig,
    UpdateBatch,
    available_engines,
    get_engine,
)

_INF = float(INF)


def masked_slab(rng, S, J, z):
    """A random mid-relaxation grouped problem with every mask in play."""
    adj = rng.uniform(1.0, 50.0, (S, z, z)).astype(np.float32)
    adj[rng.random((S, z, z)) > 0.3] = _INF
    for s in range(S):
        np.fill_diagonal(adj[s], 0.0)
    init = np.full((S, J, z), _INF, np.float32)
    for s in range(S):
        for j in range(J):
            init[s, j, rng.integers(z)] = 0.0
    bv = rng.random((S, J, z)) < 0.05
    so = np.zeros((S, J, z), bool)
    for s in range(S):
        for j in range(J):
            if rng.random() < 0.7:  # some rows spur-less
                so[s, j, rng.integers(z)] = True
    bn = rng.random((S, J, z)) < 0.1
    cap = rng.uniform(40.0, 90.0, (S, J)).astype(np.float32)
    # padded rows: all-INF init, no spur — must no-op through the solve
    init[:, J - 1, :] = _INF
    so[:, J - 1, :] = False
    return adj, init, bv, so, bn, cap


class TestSlabLayout:
    def test_engine_layouts(self):
        assert get_engine("dense_bf").layout is JNP_LAYOUT
        assert get_engine("pallas_bf").layout is PALLAS_LAYOUT
        assert get_engine("dense_bf").lane == 8
        assert get_engine("pallas_bf").lane == 128
        assert get_engine("pyen").layout is JNP_LAYOUT  # packs nothing

    def test_align_rules(self):
        assert JNP_LAYOUT.align_z(20) == 24
        assert JNP_LAYOUT.align_z(24) == 24
        assert JNP_LAYOUT.align_j(3) == 3
        assert PALLAS_LAYOUT.align_z(20) == 128
        assert PALLAS_LAYOUT.align_z(129) == 256
        assert PALLAS_LAYOUT.align_j(3) == 8
        assert PALLAS_LAYOUT.align_j(9) == 16

    def test_jnp_bucket_shape_matches_legacy_rule(self):
        """The moved hot-row packer reproduces the pre-layout behavior:
        pow2 candidates, padded-area cost Σ ceil(n/J)·J with the +1
        adjacency-duplication term, S a pow2 multiple of s_multiple."""
        def legacy(per_row_counts, s_multiple):
            pow2 = lambda n: 1 << (n - 1).bit_length() if n > 1 else 1  # noqa: E731
            j_max = pow2(max(per_row_counts))
            best, j = None, 1
            while j <= j_max:
                s_need = sum(-(-n // j) for n in per_row_counts)
                s_pad = pow2(s_need)
                if s_pad % s_multiple:
                    s_pad = -(-s_pad // s_multiple) * s_multiple
                cost = s_pad * (j + 1)
                if best is None or cost < best[0]:
                    best = (cost, s_pad, j)
                j *= 2
            return best[1], best[2]

        rng = np.random.default_rng(0)
        for _ in range(50):
            counts = [int(n) for n in
                      rng.integers(1, 40, size=rng.integers(1, 9))]
            for sm in (1, 2, 4):
                assert JNP_LAYOUT.bucket_shape(counts, sm) == \
                    legacy(counts, sm)

    def test_pallas_bucket_shape_alignment(self):
        for counts in ([1], [3, 5], [40], [1, 1, 1, 17]):
            S, J = PALLAS_LAYOUT.bucket_shape(counts)
            assert J % PALLAS_LAYOUT.j_align == 0
            assert J <= PALLAS_LAYOUT.j_max
            assert sum(-(-n // J) for n in counts) <= S

    def test_hot_row_still_split(self):
        # one hot row past j_max must split across duplicate slab rows
        S, J = PALLAS_LAYOUT.bucket_shape([100])
        assert J <= 32 and S * J >= 100

    def test_invalid_layout_rejected(self):
        with pytest.raises(ValueError, match="multiple"):
            SlabLayout(name="bad", j_align=8, j_max=12)
        with pytest.raises(ValueError, match="≥ 1"):
            SlabLayout(name="bad", lane=0)

    def test_pack_subgraphs_takes_layout(self):
        g = grid_road_network(6, 6, seed=0)
        d = DTLP.build(g, z=12, xi=4)
        tight = pack_subgraphs(d.partition, g.w, layout=JNP_LAYOUT)
        wide = pack_subgraphs(d.partition, g.w, layout=PALLAS_LAYOUT)
        assert tight.z % 8 == 0 and tight.z < 128
        assert wide.z % 128 == 0
        # identical entries where both are real
        nv = int(tight.nv.max())
        np.testing.assert_array_equal(
            tight.adj[:, :nv, :nv], wide.adj[:, :nv, :nv]
        )


class TestBackendParity:
    @settings(max_examples=4, deadline=None)
    @given(st.integers(0, 1000), st.sampled_from([24, 40, 128]))
    def test_solve_grouped_byte_identical(self, seed, z):
        """Pallas fixed point == jnp bf_solve_grouped, bitwise — masks,
        caps, padded rows, and tight-lane (non-128) z all in play."""
        rng = np.random.default_rng(seed)
        args = [jnp.asarray(x) for x in masked_slab(rng, 2, 3, z)]
        dj, pj = JnpBackend().solve_grouped(*args)
        dp, pp = PallasBackend(interpret=True).solve_grouped(*args)
        np.testing.assert_array_equal(np.asarray(dj), np.asarray(dp))
        np.testing.assert_array_equal(np.asarray(pj), np.asarray(pp))

    @pytest.mark.parametrize("seed,z", [(0, 24), (1, 40), (2, 128)])
    def test_solve_grouped_byte_identical_fixed(self, seed, z):
        """Deterministic leg of the parity sweep (runs without
        hypothesis): bitwise dist AND parents agreement per z class —
        tight-lane (24/40, exercising the kernel's internal padding)
        and native 128-lane."""
        rng = np.random.default_rng(seed)
        args = [jnp.asarray(x) for x in masked_slab(rng, 2, 3, z)]
        dj, pj = JnpBackend().solve_grouped(*args)
        dp, pp = PallasBackend(interpret=True).solve_grouped(*args)
        np.testing.assert_array_equal(np.asarray(dj), np.asarray(dp))
        np.testing.assert_array_equal(np.asarray(pj), np.asarray(pp))

    def test_grouped_ksp_backend_parity(self):
        """Whole lockstep-Yen rounds agree path-for-path per backend."""
        from repro.dist.grouped_yen import grouped_ksp

        g = grid_road_network(6, 6, seed=1)
        d = DTLP.build(g, z=12, xi=4)
        jnp_slab = pack_subgraphs(d.partition, g.w, layout=JNP_LAYOUT)
        pl_slab = pack_subgraphs(d.partition, g.w, layout=PALLAS_LAYOUT)
        tasks = []
        for row in range(min(2, jnp_slab.n_sub)):
            sg = d.partition.subgraphs[int(jnp_slab.gids[row])]
            tasks.append((row, 0, sg.nv - 1))
        want = grouped_ksp(jnp_slab.adj, tasks, 3, backend=JnpBackend())
        got = grouped_ksp(pl_slab.adj, tasks, 3,
                          backend=PallasBackend(interpret=True))
        assert got == want

    def test_zero_tasks_any_backend(self):
        from repro.dist.grouped_yen import grouped_ksp

        adj = np.zeros((1, 8, 8), np.float32)
        assert grouped_ksp(adj, [], 3,
                           backend=PallasBackend(interpret=True)) == []


class TestInterpretRule:
    """Pallas runs interpreted on the CPU, compiled on a TPU, and any
    other platform is an error rather than a silent fallback."""

    @pytest.mark.parametrize("platform,interpreted",
                             [("cpu", True), ("tpu", False)])
    def test_platform_picks_mode(self, monkeypatch, platform, interpreted):
        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        assert PallasBackend().interpreted is interpreted

    def test_other_platform_raises(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="'gpu'"):
            PallasBackend().interpreted

    def test_forced_mode_wins(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        assert PallasBackend(interpret=True).interpreted is True
        assert PallasBackend(interpret=False).interpreted is False


class TestPallasEngineEndToEnd:
    """Tier-1 serving scenario: queries + an UpdateBatch epoch barrier,
    ``pallas_bf`` (interpret on CPU) vs ``dense_bf`` — byte-identical
    paths AND epochs (the issue's acceptance scenario)."""

    def _scenario(self, engine):
        g = grid_road_network(6, 6, seed=0)
        d = DTLP.build(g, z=12, xi=4)
        svc = KSPService(d, ServiceConfig(engine=engine, n_workers=2,
                                          max_in_flight=4))
        rng = np.random.default_rng(7)
        qs = [tuple(map(int, rng.choice(g.n, 2, replace=False)))
              for _ in range(4)]
        stream = WeightUpdateStream(g, alpha=0.5, tau=0.5, seed=5)
        out = []
        # two concurrent queries before the barrier...
        t1 = svc.submit(QueryRequest(*qs[0], k=3))
        t2 = svc.submit(QueryRequest(*qs[1], k=3))
        svc.drain()
        out += [(t1.result.paths, t1.result.epoch),
                (t2.result.paths, t2.result.epoch)]
        # ...an UpdateBatch epoch barrier...
        new_epoch = svc.update(UpdateBatch(*stream.next_batch()))
        assert new_epoch == 1
        # ...and two more answered at the new epoch
        for s, t in qs[2:]:
            r = svc.query(s, t, 3)
            out.append((r.paths, r.epoch))
        return out

    def test_registered_and_selectable(self):
        assert "pallas_bf" in available_engines()
        spec = get_engine("pallas_bf")
        assert spec.packs_slab and spec.backend.name == "pallas"
        ServiceConfig(engine="pallas_bf")  # config-level selection works

    def test_paths_and_epochs_byte_identical(self):
        want = self._scenario("dense_bf")
        got = self._scenario("pallas_bf")
        assert got == want
        assert [e for _, e in got] == [0, 0, 1, 1]  # barrier ordering


class TestDeviceResidentSlabs:
    """Acceptance: per-worker slabs stay on device across scheduler
    ticks — the steady-state query path gathers adjacency rows from the
    resident mirror instead of re-transferring the slab per dispatch."""

    def test_steady_state_rounds_never_stage_from_host(self):
        from repro.engine.layout import TRANSFER_STATS, reset_transfer_stats

        g = grid_road_network(6, 6, seed=0)
        d = DTLP.build(g, z=12, xi=4)
        svc = KSPService(d, ServiceConfig(engine="dense_bf", n_workers=2,
                                          max_in_flight=4))
        for w in svc.cluster.workers:
            if w.slab is not None:
                assert w.slab.adj_dev is not None  # placed once, at init
        rng = np.random.default_rng(11)
        reset_transfer_stats()
        for _ in range(3):
            s, t = map(int, rng.choice(g.n, 2, replace=False))
            svc.query(s, t, 3)
        assert TRANSFER_STATS["device_rounds"] > 0
        assert TRANSFER_STATS["host_rounds"] == 0

    def test_mirror_tracks_patches(self):
        """Barrier and streaming patches keep the device mirror bitwise
        in sync with the host slab (the mirror is patched functionally,
        never re-staged)."""
        from repro.dist.cluster import Cluster

        g = grid_road_network(6, 6, seed=2)
        d = DTLP.build(g, z=12, xi=4)
        cl = Cluster(d, n_workers=2, engine="dense_bf")
        stream = WeightUpdateStream(g, alpha=0.5, tau=0.5, seed=3)
        cl.apply_updates(*stream.next_batch())
        cl.apply_updates_streaming(*stream.next_batch())
        for w in cl.workers:
            if w.slab is None:
                continue
            S = w.slab.adj.shape[0]
            np.testing.assert_array_equal(
                np.asarray(w.slab.adj_dev)[:S], w.slab.adj
            )
            # the double buffer's mirror stayed at the previous epoch
            S0 = w.prev_slab.adj.shape[0]
            np.testing.assert_array_equal(
                np.asarray(w.prev_slab.adj_dev)[:S0], w.prev_slab.adj
            )


needs_devices = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs ≥2 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=N)",
)


@needs_devices
class TestMeshParityLadder:
    """The tentpole's parity ladder on a real (2,1) device mesh: solve →
    grouped-Yen → end-to-end KSPService, each leg byte-identical to the
    single-device reference, for BOTH slab backends."""

    @pytest.fixture(scope="class")
    def mesh(self):
        return jax.sharding.Mesh(
            np.array(jax.devices()[:2]).reshape(2, 1), ("data", "model")
        )

    @pytest.mark.parametrize("backend", [JnpBackend(),
                                         PallasBackend(interpret=True)],
                             ids=["jnp", "pallas"])
    def test_solve_level(self, mesh, backend):
        from repro.dist.shard_refine import make_refine_fn

        rng = np.random.default_rng(5)
        args = [jnp.asarray(x) for x in masked_slab(rng, 4, 3, 24)]
        d_ref, p_ref = backend.solve_grouped(*args)
        d_m, p_m = make_refine_fn(mesh, backend=backend)(*args)
        np.testing.assert_array_equal(np.asarray(d_ref), np.asarray(d_m))
        np.testing.assert_array_equal(np.asarray(p_ref), np.asarray(p_m))

    @pytest.mark.parametrize("engine", ["dense_bf", "pallas_bf"])
    def test_grouped_yen_level(self, mesh, engine):
        from repro.dist.grouped_yen import grouped_ksp

        spec = get_engine(engine)
        g = grid_road_network(6, 6, seed=1)
        d = DTLP.build(g, z=12, xi=4)
        slab = pack_subgraphs(d.partition, g.w, layout=spec.layout)
        tasks = []
        for row in range(min(2, slab.n_sub)):
            sg = d.partition.subgraphs[int(slab.gids[row])]
            tasks.append((row, 0, sg.nv - 1))
        want = grouped_ksp(slab.adj, tasks, 3, backend=spec.backend)
        solver, s_multiple = spec.make_mesh_solver(mesh, ("data", "model"))
        got = grouped_ksp(slab.adj, tasks, 3, solver=solver,
                          s_multiple=s_multiple, backend=spec.backend)
        assert got == want

    @pytest.mark.parametrize("engine", ["dense_bf", "pallas_bf"])
    def test_service_level(self, mesh, engine):
        def scenario(mesh_arg):
            g = grid_road_network(6, 6, seed=0)
            d = DTLP.build(g, z=12, xi=4)
            svc = KSPService(d, ServiceConfig(
                engine=engine, n_workers=2, max_in_flight=4,
                mesh=mesh_arg,
            ))
            rng = np.random.default_rng(7)
            qs = [tuple(map(int, rng.choice(g.n, 2, replace=False)))
                  for _ in range(4)]
            stream = WeightUpdateStream(g, alpha=0.5, tau=0.5, seed=5)
            out = []
            for s, t in qs[:2]:
                r = svc.query(s, t, 3)
                out.append((r.paths, r.epoch))
            svc.update(UpdateBatch(*stream.next_batch()))
            for s, t in qs[2:]:
                r = svc.query(s, t, 3)
                out.append((r.paths, r.epoch))
            return out

        want = scenario(None)
        got = scenario(mesh)
        assert got == want
        assert [e for _, e in got] == [0, 0, 1, 1]
