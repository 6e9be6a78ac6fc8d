"""Staged host pipeline (BatchPlan IR): bitwise equivalence against the
pre-refactor monolithic prepare() (reconstructed here exactly as the old
engine composed it — per-batch einsum edge extras included), subgraph-row
cache semantics, frontier-exact dual invalidation, automatic repin
triggers, and the SGC lowering."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.batchplan import BatchPlan
from repro.core.config import ServingConfig
from repro.core.dispatch import DispatchConfig
from repro.core.engine import DecoupledEngine
from repro.core.ini import ini_batch
from repro.core.scheduler import PipelineScheduler
from repro.core.subgraph import (assemble_batch, batch_from_node_lists,
                                 build_batch)
from repro.gnn.model import GNNConfig, init_gnn
from repro.graphs.synthetic import get_graph, zipf_traffic
from repro.serve.gnn_server import GNNServer
from repro.store import StorePolicy, SubgraphRowCache

KINDS = ("gcn", "sage", "gat", "appnp")
N = 16
C = 4
TARGETS = np.arange(8)


@pytest.fixture(scope="module")
def graph():
    return get_graph("flickr", scale=0.02, seed=1)   # ~1.8k vertices


def _cfg(kind, graph, n_layers=2):
    return GNNConfig(kind=kind, n_layers=n_layers, receptive_field=N,
                     f_in=graph.feature_dim)


def legacy_prepare(eng, targets):
    """The PRE-REFACTOR monolithic prepare(), reconstructed: one blob of
    INI + induced-subgraph build + feature payload, with the sg-mode edge
    extras recovered per batch by densifying adj (the old einsum path)."""
    cfg = eng.cfg
    n = cfg.receptive_field
    node_lists = ini_batch(eng.graph, [int(t) for t in targets], n,
                           cfg.ppr_alpha, cfg.ppr_eps, num_threads=1)
    src = eng._fsource
    sb = batch_from_node_lists(eng.graph, targets, node_lists, n,
                               eng.e_pad,
                               build_feats=src.needs_host_feats)
    d = {"mask": sb.mask}
    for k in eng.adj_keys:
        d[k] = sb.adj if k == "adj" else sb.adj_mean
    if eng.needs_edges:
        self_w = sb.adj[:, np.arange(sb.n), np.arange(sb.n)]
        indeg = np.einsum("cij->ci", (sb.adj_mean > 0).astype(np.float32))
        d.update(edge_src=sb.edge_src, edge_dst=sb.edge_dst,
                 edge_w=sb.edge_w, self_w=self_w.astype(np.float32))
        valid = sb.edge_w != 0
        dst_deg = np.take_along_axis(
            np.maximum(indeg, 1.0), sb.edge_dst.astype(np.int64), axis=1)
        d["edge_w_mean"] = np.where(valid, 1.0 / dst_deg, 0.0
                                    ).astype(np.float32)
    payload, _ = src.host_payload(
        node_lists, n, sb.feats if src.needs_host_feats else None)
    d.update(payload)
    return d


class TestStagedEqualsMonolithic:
    @pytest.mark.parametrize("impl", ("xla", "pallas"))
    @pytest.mark.parametrize("kind", KINDS)
    def test_bitwise_equal_embeddings(self, graph, kind, impl):
        """Acceptance: the staged pipeline (the default submit_chunk /
        infer path) produces bitwise-identical embeddings to the
        pre-refactor monolithic prepare() for every kind x impl.
        mode="sg" forces the edge arrays (and their carried extras) into
        the datapath, so the CSR-direct self_w/edge_w_mean are covered."""
        cfg = _cfg(kind, graph)
        params = init_gnn(cfg, jax.random.PRNGKey(2))
        with DecoupledEngine(graph, cfg, params=params, batch_size=C,
                             impl=impl, mode="sg", num_threads=1) as eng:
            staged = np.asarray(eng.submit_chunk(TARGETS[:C]).result())
            legacy = np.asarray(
                eng.run_device(legacy_prepare(eng, TARGETS[:C])))
            np.testing.assert_array_equal(staged, legacy)

    def test_dense_auto_mode_equal(self, graph):
        cfg = _cfg("gcn", graph)
        with DecoupledEngine(graph, cfg, batch_size=C, seed=3,
                             num_threads=1) as eng:
            staged = eng.infer(TARGETS, overlap=True).embeddings
            legacy = np.concatenate(
                [np.asarray(eng.run_device(legacy_prepare(eng, chunk)))
                 for chunk in (TARGETS[:C], TARGETS[C:])])
            np.testing.assert_array_equal(staged, legacy)

    def test_host_fn_spelling_still_pipelines(self, graph):
        """The one-stage back-compat spelling: a PipelineScheduler built
        from a plain host_fn behaves like before and reports its host
        time under the "host" stage label."""
        cfg = _cfg("gcn", graph)
        with DecoupledEngine(graph, cfg, batch_size=C, seed=3,
                             num_threads=1) as eng:
            staged = eng.infer(TARGETS, overlap=True).embeddings
            mono = PipelineScheduler(eng.prepare, eng.run_device, depth=2)
            outs, stats = mono.run([TARGETS[:C], TARGETS[C:]])
            mono.close()
            np.testing.assert_array_equal(
                staged, np.concatenate([np.asarray(o) for o in outs]))
            assert list(stats.service_times) == ["host"]

    def test_stage_times_reported(self, graph):
        cfg = _cfg("gcn", graph)
        with DecoupledEngine(graph, cfg, batch_size=C, seed=3,
                             num_threads=1) as eng:
            eng.infer(TARGETS, overlap=True)
            s = eng.scheduler.stats.summary()
            times = s["stages"]["times"]
            assert set(times) == {"select", "build", "pack"}
            assert all(v > 0 for v in times.values())
            assert "build_hit_rate" in s["stages"]
            # per-stage sums make up the recorded host time
            assert sum(times.values()) == pytest.approx(
                s["latency"]["t_host"], rel=0.05)

    def test_plan_artifact_fields(self, graph):
        """plan() exposes the full BatchPlan: every stage's output is
        inspectable (the host-side mirror of InferenceResult.decision)."""
        cfg = _cfg("gcn", graph)
        pol = StorePolicy(nbr_cache="lru", nbr_capacity=32)
        with DecoupledEngine(graph, cfg, batch_size=C, seed=3,
                             store=pol, num_threads=1) as eng:
            plan = eng.plan(TARGETS[:C])
            assert isinstance(plan, BatchPlan)
            assert len(plan.node_lists) == C
            assert len(plan.rows) == C
            assert plan.rows[0].adj.shape == (N, N)
            assert plan.device is not None
            assert plan.device["mask"].shape == (C, N)
            assert plan.nbr_misses == C    # cold cache
            # frontiers cached for exact invalidation
            assert all(f is not None for f in plan.frontiers.values())


PACK_MODES = {
    "dense-auto": dict(mode="auto"),
    "sg": dict(mode="sg"),
    "dispatch": dict(mode="auto", dispatch=DispatchConfig(
        warmup_passes=0, autotune_blocks=False)),
}


class TestPackBuildsWhatIsRead:
    """Pack stacks only the arrays the compiled program reads, straight
    from the built rows: the device dict is bitwise the full-batch
    spelling's (``assemble_batch`` + ``device_batch``) on every key."""
    CP = 8             # 3 real targets + a padded tail of 5

    def _engine(self, graph, kind, features="resident", **serving):
        return DecoupledEngine(
            graph, _cfg(kind, graph), config=ServingConfig(
                batch_size=self.CP, seed=3, num_threads=1,
                store=StorePolicy(features=features, nbr_cache="lru",
                                  nbr_capacity=64), **serving))

    @staticmethod
    def _packed(eng, targets):
        """Select and Build, then Pack; also the device dict Pack used
        to ship for the same rows: the full batch, cut to ``mask``, the
        program's adjacencies and (when it reads them) the edge arrays,
        plus the store's payload."""
        plan = BatchPlan(targets=eng.pad_targets(np.asarray(targets)))
        for stage in eng.stages[:-1]:
            plan = stage.run(plan)
        n, src = eng.cfg.receptive_field, eng._fsource
        sb = assemble_batch(eng.graph, plan.targets, plan.node_lists,
                            plan.rows, n, eng.e_pad,
                            build_feats=src.needs_host_feats)
        want = {"mask": sb.mask}
        for k in eng.adj_keys:
            want[k] = sb.adj if k == "adj" else sb.adj_mean
        if eng.needs_edges:
            want.update(edge_src=sb.edge_src, edge_dst=sb.edge_dst,
                        edge_w=sb.edge_w, self_w=sb.self_w,
                        edge_w_mean=sb.edge_w_mean)
        assert eng.device_batch(sb, include_feats=False).keys() \
            == want.keys()
        payload, _ = src.host_payload(
            plan.node_lists, n, sb.feats if src.needs_host_feats else None)
        want.update(payload)
        return eng.stages[-1].run(plan).device, want

    @pytest.mark.parametrize("features", ("dense", "resident"))
    @pytest.mark.parametrize("serving", tuple(PACK_MODES))
    @pytest.mark.parametrize("kind", ("gcn", "gat", "sage"))
    def test_device_dict_bitwise_full_batch(self, graph, kind, serving,
                                            features):
        with self._engine(graph, kind, features,
                          **PACK_MODES[serving]) as eng:
            got, want = self._packed(eng, TARGETS[:3])
            assert got.keys() == want.keys()
            for k, a in want.items():
                assert got[k].dtype == a.dtype, k
                np.testing.assert_array_equal(got[k], a, err_msg=k)
            if serving != "dense-auto":
                assert "edge_src" in got and "self_w" in got

    def test_dense_gcn_packs_only_adj_and_mask(self, graph):
        with self._engine(graph, "gcn") as eng:
            got, _ = self._packed(eng, TARGETS[:3])
            assert set(got) == {"mask", "adj",
                                *eng._fsource.payload_keys}
            s = eng.scheduler.stats
            assert s.bytes_packed == s.bytes_shipped \
                == sum(a.nbytes for a in got.values())
            assert eng.scheduler.stats.summary()["store"][
                "bytes_packed"] == s.bytes_packed


class TestSubgraphRowCache:
    def _engine(self, graph, **pol):
        cfg = _cfg("gcn", graph)
        return DecoupledEngine(graph, cfg, batch_size=C, seed=4,
                               num_threads=1,
                               store=StorePolicy(nbr_cache="lru",
                                                 nbr_capacity=64, **pol))

    def test_hit_batch_identical_to_cold_build(self, graph):
        """Acceptance: a subgraph-row-cache hit batch is bitwise-identical
        to the cold build, and the Build stage was actually skipped."""
        eng = self._engine(graph)
        cold = eng.infer(TARGETS, overlap=False).embeddings
        assert eng.sg_cache.misses == len(TARGETS)
        hot = eng.infer(TARGETS, overlap=False).embeddings
        np.testing.assert_array_equal(cold, hot)
        assert eng.sg_cache.hits == len(TARGETS)
        s = eng.scheduler.stats
        assert s.build_hits == len(TARGETS)
        assert s.build_hit_rate == 0.5
        eng.close()

    def test_auto_follows_nbr_cache(self, graph):
        eng = self._engine(graph)                     # auto -> on
        assert eng.sg_cache is not None
        eng.close()
        eng = self._engine(graph, subgraph_rows="off")
        assert eng.sg_cache is None
        emb = eng.infer(TARGETS[:C], overlap=False).embeddings
        assert emb.shape == (C, eng.cfg.f_hidden)
        eng.close()
        cfg = _cfg("gcn", graph)
        eng = DecoupledEngine(graph, cfg, batch_size=C, num_threads=1)
        assert eng.sg_cache is None                   # no nbr cache
        eng.close()

    def test_rows_on_without_nbr_cache(self, graph):
        """subgraph_rows="on" alone still skips Build (the node list is
        deterministic in the key even when Select recomputes it)."""
        cfg = _cfg("gcn", graph)
        eng = DecoupledEngine(graph, cfg, batch_size=C, seed=4,
                              num_threads=1,
                              store=StorePolicy(subgraph_rows="on"))
        a = eng.infer(TARGETS[:C], overlap=False).embeddings
        b = eng.infer(TARGETS[:C], overlap=False).embeddings
        np.testing.assert_array_equal(a, b)
        assert eng.sg_cache.hits == C
        eng.close()

    def test_invalidate_drops_both_levels(self, graph):
        """Acceptance: invalidate() drops BOTH the neighborhood entry and
        the subgraph-row entry (frontier-exact on both)."""
        eng = self._engine(graph)
        eng.infer(TARGETS, overlap=False)
        assert len(eng.nbr_cache) == len(TARGETS)
        assert len(eng.sg_cache) == len(TARGETS)
        dropped = eng.invalidate(TARGETS)     # every push touches its
        assert dropped == len(TARGETS)        # own target
        assert len(eng.nbr_cache) == 0
        assert len(eng.sg_cache) == 0
        assert eng.sg_cache.invalidations == len(TARGETS)
        rep = eng.store_report()
        assert rep["subgraph_cache"]["invalidations"] == len(TARGETS)
        eng.close()

    def test_graph_update_recompute_matches_fresh_engine(self, graph):
        """Edge updates flow through both cache levels: post-update
        inference equals a fresh engine over the updated graph."""
        import copy
        g = copy.deepcopy(graph)
        cfg = _cfg("gcn", g)
        eng = DecoupledEngine(g, cfg, batch_size=C, seed=4, num_threads=1,
                              store=StorePolicy(nbr_cache="lru",
                                                nbr_capacity=64))
        eng.infer(TARGETS, overlap=False)              # warm both caches
        deg = g.degrees
        hubs = np.argsort(-deg)[:2]
        g.apply_edge_updates(insert=[(int(TARGETS[0]), int(hubs[0])),
                                     (int(hubs[1]), int(TARGETS[1]))])
        after = eng.infer(TARGETS, overlap=False).embeddings
        fresh = DecoupledEngine(g, cfg, params=eng.params, batch_size=C,
                                num_threads=1)
        np.testing.assert_array_equal(
            after, fresh.infer(TARGETS, overlap=False).embeddings)
        fresh.close()
        eng.close()

    def test_put_dropped_across_invalidate_generation(self):
        """A row built before an invalidate() must not land (same
        generation contract as the neighborhood cache)."""
        from repro.core.subgraph import build_subgraph_rows
        g = get_graph("flickr", scale=0.01, seed=0)
        cache = SubgraphRowCache(capacity=8)
        rows = build_subgraph_rows(g, np.arange(4), 8, 16)
        gen = cache.generation
        cache.invalidate([1])                 # update lands mid-build
        cache.put(("k",), rows, generation=gen,
                  frontier=np.arange(4))
        assert ("k",) not in cache
        cache.put(("k",), rows, generation=cache.generation,
                  frontier=np.arange(4))
        assert ("k",) in cache
        assert cache.get(("k",)).adj.flags.writeable is False


class TestAutoRepin:
    def _stream(self, eng, chunks):
        return [np.asarray(eng.submit_chunk(c).result()) for c in chunks]

    def test_fires_every_k_batches(self, graph):
        """Acceptance: repin_every=K fires on the pipeline's completion
        path at exactly floor(batches / K) times, and never corrupts an
        in-flight batch (outputs bitwise-equal to a no-repin engine)."""
        cfg = _cfg("gcn", graph)
        params = init_gnn(cfg, jax.random.PRNGKey(5))
        budget = 48 * graph.feature_dim * 4
        pol = StorePolicy(features="resident", hbm_budget_bytes=budget,
                          nbr_cache="lru", repin_every=3)
        traffic = zipf_traffic(graph, 40, a=1.1, seed=3)
        chunks = [traffic[i:i + C] for i in range(0, 40, C)]
        eng = DecoupledEngine(graph, cfg, params=params, batch_size=C,
                              store=pol, num_threads=1)
        outs = self._stream(eng, chunks)
        eng.scheduler.flush()
        eng.drain_repins()           # rebalances run on their own worker
        assert eng.auto_repins == len(chunks) // 3
        assert eng._fsource.repins == eng.auto_repins
        assert eng.store_report()["auto_repins"] == eng.auto_repins
        # same store strategy WITHOUT the trigger: outputs must match
        # bitwise — residency generation changes never touch the values
        ref = DecoupledEngine(
            graph, cfg, params=params, batch_size=C, num_threads=1,
            store=StorePolicy(features="resident",
                              hbm_budget_bytes=budget, nbr_cache="lru"))
        ref_outs = self._stream(ref, chunks)
        for a, b in zip(outs, ref_outs):
            np.testing.assert_array_equal(a, b)
        ref.close()
        eng.close()

    def test_hit_floor_trigger(self, graph):
        """repin_hit_floor: a resident hit rate below the floor triggers
        a repin without a batch-count schedule."""
        cfg = _cfg("gcn", graph)
        budget = 16 * graph.feature_dim * 4   # tiny: most lookups miss
        pol = StorePolicy(features="resident", hbm_budget_bytes=budget,
                          repin_hit_floor=1.0)
        eng = DecoupledEngine(graph, cfg, batch_size=C, seed=5,
                              store=pol, num_threads=1)
        eng.infer(TARGETS, overlap=False)      # serial path fires it too
        assert eng.auto_repins >= 1
        eng.drain_repins()
        assert eng._fsource.repins == eng.auto_repins
        # a floor that can never be met backs off instead of rebuilding
        # the table every batch
        assert eng._floor_wait > 1
        eng.close()

    def test_repin_promotes_observed_mass(self, graph):
        """Single-device PPR-mass feedback: after skewed traffic, repin
        residency covers the observed rows better than the degree prior
        (hit rate does not regress), bitwise-equal embeddings."""
        cfg = _cfg("gcn", graph)
        params = init_gnn(cfg, jax.random.PRNGKey(6))
        budget = 64 * graph.feature_dim * 4
        pol = StorePolicy(features="resident", hbm_budget_bytes=budget,
                          nbr_cache="lru")
        eng = DecoupledEngine(graph, cfg, params=params, batch_size=C,
                              store=pol, num_threads=1)
        traffic = zipf_traffic(graph, 64, a=1.1, seed=4)
        emb0 = eng.infer(traffic[:32], overlap=False).embeddings
        st = eng._fsource
        lk0, res0 = st.lookups, st.resident_lookups
        rep = eng.repin()
        assert rep["resident_rows"] > 0 and "mass_covered" in rep
        emb1 = eng.infer(traffic[:32], overlap=False).embeddings
        np.testing.assert_array_equal(emb0, emb1)  # residency-invariant
        after = (st.resident_lookups - res0) / (st.lookups - lk0)
        assert after >= (res0 / lk0) - 1e-9
        eng.close()

    def test_policy_validation(self):
        with pytest.raises(ValueError, match="repin"):
            StorePolicy(repin_every=4)                 # dense: no repin
        with pytest.raises(ValueError, match="repin_hit_floor"):
            StorePolicy(features="resident", repin_hit_floor=1.5)
        with pytest.raises(ValueError, match="subgraph_rows"):
            StorePolicy(subgraph_rows="maybe")
        pol = StorePolicy(features="resident", repin_every=8,
                          nbr_cache="lru")
        assert pol.describe()["repin_every"] == 8
        assert pol.cache_subgraph_rows is True

    def test_inflight_snapshot_survives_repin(self, graph):
        """A payload prepared before repin() gathers against ITS residency
        generation, not the new one (single-device mirror of the sharded
        snapshot test)."""
        cfg = _cfg("gcn", graph)
        budget = 48 * graph.feature_dim * 4
        pol = StorePolicy(features="resident", hbm_budget_bytes=budget,
                          nbr_cache="lru")
        eng = DecoupledEngine(graph, cfg, batch_size=8, seed=7,
                              store=pol, num_threads=1)
        node_lists, _, _ = eng._node_lists([int(t) for t in TARGETS])
        payload, _ = eng._fsource.host_payload(node_lists, N)  # in flight
        eng.infer(zipf_traffic(graph, 32, a=1.2, seed=5), overlap=False)
        for _ in range(3):
            eng.repin()                        # several generations later
        # the held payload gathers against ITS generation; a fresh
        # payload (new slots, new generation) must yield the same rows
        stale = np.asarray(eng._fsource.device_feats(payload))
        fresh_payload, _ = eng._fsource.host_payload(node_lists, N)
        fresh = np.asarray(eng._fsource.device_feats(fresh_payload))
        np.testing.assert_array_equal(stale, fresh)
        eng.close()


class TestSGCLowering:
    def test_matches_explicit_recurrence(self, graph):
        """sgc = K propagation steps + one linear map: the program output
        equals the explicit S^K X W recurrence (f64 reference, and
        bitwise against the same-order jnp recurrence)."""
        cfg = GNNConfig(kind="sgc", n_layers=3, receptive_field=N,
                        f_in=graph.feature_dim)     # K = 2 propagations
        params = init_gnn(cfg, jax.random.PRNGKey(8))
        with DecoupledEngine(graph, cfg, params=params, batch_size=C,
                             mode="dense", num_threads=1) as eng:
            emb = eng.infer(TARGETS[:C], overlap=False).embeddings
        sb = build_batch(graph, TARGETS[:C], N, e_pad=N * (N - 1),
                         num_threads=1)
        w = np.asarray(params["layer0"]["w"], np.float64)
        z = (sb.feats.astype(np.float64) @ w) * sb.mask[..., None]
        for _ in range(cfg.n_layers - 1):
            z = np.einsum("cij,cjf->cif", sb.adj.astype(np.float64), z)
        ref64 = np.where(sb.mask[..., None] > 0, z, -1e30).max(axis=1)
        np.testing.assert_allclose(emb, ref64, rtol=1e-4, atol=1e-5)
        # bitwise against the identical-op jnp recurrence
        zj = jnp.einsum("cnf,fg->cng", jnp.asarray(sb.feats),
                        jnp.asarray(params["layer0"]["w"]),
                        preferred_element_type=jnp.float32)
        zj = zj * sb.mask[..., None]
        for _ in range(cfg.n_layers - 1):
            zj = jnp.einsum("cij,cjf->cif", jnp.asarray(sb.adj), zj,
                            preferred_element_type=jnp.float32)
        refj = jnp.max(jnp.where(sb.mask[..., None] > 0, zj, -1e30),
                       axis=1)
        np.testing.assert_array_equal(emb, np.asarray(refj))

    def test_sgc_sg_mode_matches_dense(self, graph):
        cfg = GNNConfig(kind="sgc", n_layers=3, receptive_field=N,
                        f_in=graph.feature_dim)
        params = init_gnn(cfg, jax.random.PRNGKey(9))
        embs = {}
        for mode in ("dense", "sg"):
            with DecoupledEngine(graph, cfg, params=params, batch_size=C,
                                 mode=mode, num_threads=1,
                                 e_pad=N * (N - 1)) as eng:
                embs[mode] = eng.infer(TARGETS[:C],
                                       overlap=False).embeddings
        np.testing.assert_allclose(embs["dense"], embs["sg"],
                                   rtol=1e-5, atol=1e-5)

    def test_served_under_shared_dse_plan(self, graph):
        """sgc admits next to gcn under ONE explored DSEPlan and serves
        correct embeddings through the staged pipeline."""
        cfg_g = _cfg("gcn", graph)
        cfg_s = GNNConfig(kind="sgc", n_layers=3, receptive_field=N,
                          f_in=graph.feature_dim)
        e_g = DecoupledEngine(graph, cfg_g, batch_size=C, seed=10,
                              num_threads=1)
        e_s = DecoupledEngine(graph, cfg_s, batch_size=C, seed=11,
                              num_threads=1)
        standalone = e_s.infer(TARGETS[:C], overlap=False).embeddings
        srv = GNNServer(max_wait_s=0.01)
        srv.register("gcn", e_g).register("sgc", e_s)
        srv.start()
        try:
            reqs = [srv.submit(int(t), model) for t in TARGETS[:C]
                    for model in ("gcn", "sgc")]
            srv.drain(reqs, timeout=60)
            got = {(r.model, r.target): r.embedding for r in reqs}
            for i, t in enumerate(TARGETS[:C]):
                np.testing.assert_array_equal(got[("sgc", int(t))],
                                              standalone[i])
            rep = srv.report()
            assert rep["models"]["sgc"]["kind"] == "sgc"
            assert "times" in rep["models"]["sgc"]["stages"]
        finally:
            srv.stop()
            e_g.close()
            e_s.close()


class TestPipelinedScheduling:
    def test_stages_overlap_across_batches(self):
        """Stage i of batch k runs concurrently with stage i+1 of batch
        k-1: with two stages that each sleep, two batches take ~3 slots
        pipelined, not 4 serial."""
        log = []
        lock = threading.Lock()

        class _St:
            def __init__(self, name):
                self.name = name
                self.workers = 1

            def run(self, v):
                import time as _t
                with lock:
                    log.append((self.name, v))
                _t.sleep(0.05)
                return v

            def close(self):
                pass

        s = PipelineScheduler([_St("a"), _St("b")],
                              lambda v: jnp.asarray(v), depth=2)
        t0 = [s.submit(i) for i in range(3)]
        outs = [t.result() for t in t0]
        assert [int(np.asarray(o)) for o in outs] == [0, 1, 2]
        st = s.stats
        assert set(st.service_times) == {"a", "b"}
        # pipelined wall < serial sum of stage times (3 batches x 2
        # stages x 50ms serial = 300ms; pipelined ~200ms)
        assert st.t_wall < 0.9 * (st.service_times["a"]
                                  + st.service_times["b"])
        s.close()

    def test_stage_error_isolated_to_ticket(self):
        class _Boom:
            name = "boom"
            workers = 1

            def run(self, v):
                if v == 1:
                    raise ValueError("bad batch")
                return v

            def close(self):
                pass

        s = PipelineScheduler([_Boom()], lambda v: jnp.asarray(v),
                              depth=2)
        bad = s.submit(1)
        ok = s.submit(2)
        with pytest.raises(ValueError, match="bad batch"):
            bad.result(timeout=10)
        assert int(np.asarray(ok.result(timeout=10))) == 2
        s.close()
