"""Per-batch adaptive dispatch: policy, variant cache, warmup schedule,
calibration persistence, Pallas block autotune, and the bitwise-identity
contract (a dispatch-enabled run serves the same bits as the matching
forced-mode engine)."""
import numpy as np
import pytest

from repro.core.config import ServingConfig
from repro.core.dispatch import (DispatchConfig, DispatchPolicy,
                                 VariantCache, variant_key)
from repro.core.engine import DecoupledEngine
from repro.core.program import compile_steps, mux_sites, respecialize
from repro.gnn.model import GNNConfig
from repro.graphs.csr import from_edge_list
from repro.graphs.synthetic import get_graph
from repro.obs.calib import (CalibrationArtifactError, CalibrationTable,
                             WarmupSchedule, best_block, load_calibration,
                             op_label, op_mode, save_calibration)

KINDS = ("gcn", "sage", "gin", "gat")
N = 16


@pytest.fixture(scope="module")
def graph():
    return get_graph("flickr", scale=0.005, seed=1)   # ~450 vertices


def sparse_graph(v=512, edges=48, f=64, seed=0):
    """Mean degree << 1: the regime where sg aggregation wins."""
    rng = np.random.default_rng(seed)
    src = rng.choice(v, edges, replace=False)
    dst = (src + 1 + rng.integers(0, v - 1, edges)) % v
    feats = rng.standard_normal((v, f)).astype(np.float32)
    return from_edge_list(src, dst, v, feats, name="ultra-sparse")


def make_cfg(g, kind="gcn"):
    return GNNConfig(kind=kind, n_layers=2, receptive_field=N,
                     f_in=g.feature_dim, f_hidden=128)


def serve(g, cfg, params, config, targets):
    with DecoupledEngine(g, cfg, params=params, config=config) as eng:
        out = eng.infer(targets).embeddings
        rep = eng.dispatch_report()
    return out, rep


# ---------------------------------------------------------------------------


class TestVariantCache:
    def test_bounded_lru_with_counters(self):
        vc = VariantCache(capacity=2)
        fns = {}
        for k in ("a", "b", "c"):
            fns[k] = vc.get(k, lambda k=k: (lambda: k))
        assert len(vc) == 2                       # bounded
        assert vc.evictions == 1 and vc.misses == 3 and vc.hits == 0
        assert "a" not in vc.keys()               # LRU order: a evicted
        # in-flight safety: the evicted entry's holder still runs it
        assert fns["a"]() == "a"
        assert vc.get("b", lambda: None)() == "b"  # hit, no rebuild
        assert vc.hits == 1

    def test_lru_recency(self):
        vc = VariantCache(capacity=2)
        vc.get("a", lambda: "A")
        vc.get("b", lambda: "B")
        vc.get("a", lambda: "never")              # touch a -> b is LRU
        vc.get("c", lambda: "C")
        assert set(vc.keys()) == {"a", "c"}

    def test_validation(self):
        with pytest.raises(ValueError):
            VariantCache(capacity=0)
        with pytest.raises(ValueError):
            DispatchConfig(variant_capacity=0)
        with pytest.raises(ValueError):
            DispatchConfig(warmup_passes=-1)
        with pytest.raises(TypeError, match="DispatchConfig"):
            ServingConfig(dispatch=object())

    def test_variant_key_canonical(self):
        a = variant_key({"x": "sg", "y": "dense"}, {"block_f": 128})
        b = variant_key({"y": "dense", "x": "sg"}, {"block_f": 128})
        assert a == b
        assert variant_key({}, {"block_f": None}) == variant_key({}, {})


class TestWarmupSchedule:
    def test_deterministic_and_alternating(self):
        h = []
        for _ in range(2):
            ws = WarmupSchedule(passes=2, seed=7)
            seq = [ws.next_mode(9) for _ in range(5)]
            h.append(seq)
        assert h[0] == h[1]                       # seeded determinism
        assert h[0][4] is None                    # exhausted at 2*passes
        modes = h[0][:4]
        assert modes[0] != modes[1] and modes[2] != modes[3]
        assert set(modes) == {"dense", "sg"}      # both sides explored

    def test_per_bucket_state(self):
        ws = WarmupSchedule(passes=1, seed=0)
        ws.next_mode(5)
        assert ws.active(5) and not ws.active(5) is None
        ws.next_mode(5)
        assert not ws.active(5)
        assert ws.active(6)                       # other buckets untouched
        assert ws.state()["done"] == {5: 2}


class TestRespecialize:
    def test_validation(self, graph):
        cfg = make_cfg(graph)
        with DecoupledEngine(graph, cfg, config=ServingConfig(
                batch_size=2)) as eng:
            prog = eng.program
        sites = mux_sites(prog)
        assert sites                              # gcn has mux'd Aggregates
        with pytest.raises(KeyError):
            respecialize(prog, {"layer0[99]": "sg"})
        with pytest.raises(ValueError, match="no dense/sg mux"):
            respecialize(prog, {"tail[0]": "sg"})
        with pytest.raises(ValueError):
            respecialize(prog, {sites[0]: "systolic"})
        # unlisted sites keep their mode; listed flip
        flipped = respecialize(prog, {sites[0]: "sg"})
        assert dict(flipped.ops)[sites[0]].mode == "sg"
        assert flipped.specialized


# ---------------------------------------------------------------------------
# the bitwise-identity contract


class TestAdaptiveBitwise:
    @pytest.mark.parametrize("impl", ("xla", "pallas"))
    @pytest.mark.parametrize("kind", KINDS)
    def test_auto_equals_matching_forced(self, graph, kind, impl):
        """Per-batch auto on the hub-dense regime serves bit-identical
        embeddings to the forced engine of the mode it picks, for every
        kind x impl. (Warmup instrumented passes discard outputs, so
        this holds during exploration too — warmup_passes=1 keeps them
        in the loop; autotune stays off because tuned block_e regroups
        fp32 accumulation.)"""
        import jax
        from repro.gnn.model import init_gnn
        cfg = make_cfg(graph, kind)
        params = init_gnn(cfg, jax.random.PRNGKey(3))
        targets = np.arange(4)
        dc = DispatchConfig(warmup_passes=1, autotune_blocks=False)
        auto, rep = serve(graph, cfg, params, ServingConfig(
            batch_size=2, mode="auto", dispatch=dc), targets)
        # the dense regime picks dense on every mux site, so the
        # matching forced engine is the all-dense one
        assert rep["decisions"] == 2
        forced, _ = serve(graph, cfg, params, ServingConfig(
            batch_size=2, mode="dense"), targets)
        np.testing.assert_array_equal(auto, forced)

    @pytest.mark.parametrize("impl", ("xla", "pallas"))
    @pytest.mark.parametrize("kind", ("gcn", "sage"))
    def test_auto_equals_forced_sg_on_sparse(self, kind, impl):
        import jax
        from repro.gnn.model import init_gnn
        g = sparse_graph()
        cfg = make_cfg(g, kind)
        params = init_gnn(cfg, jax.random.PRNGKey(3))
        targets = np.arange(4)
        dc = DispatchConfig(warmup_passes=1, autotune_blocks=False)
        auto, rep = serve(g, cfg, params, ServingConfig(
            batch_size=2, mode="auto", impl=impl, dispatch=dc), targets)
        forced, _ = serve(g, cfg, params, ServingConfig(
            batch_size=2, mode="sg", impl=impl), targets)
        np.testing.assert_array_equal(auto, forced)


class TestMeasuredDispatch:
    def test_injected_table_forces_sg_bitwise(self, graph):
        """A table whose cells make sg cheaper flips serving to all-sg
        from the FIRST batch (no warmup consumed), bit-identical to the
        forced sg engine — measured costs really drive the mux."""
        import jax
        from repro.gnn.model import init_gnn
        cfg = make_cfg(graph)
        params = init_gnn(cfg, jax.random.PRNGKey(3))
        targets = np.arange(4)
        dc = DispatchConfig(warmup_passes=0, autotune_blocks=False)
        with DecoupledEngine(graph, cfg, params=params,
                             config=ServingConfig(batch_size=2,
                                                  mode="auto",
                                                  dispatch=dc)) as eng:
            pol = eng.dispatch
            bucket = int(2 * N).bit_length()      # C*N of this engine
            for sec, _ in eng.program.layer_sections():
                sites = [s for s in pol.sites if s.startswith(sec)]
                for mode, cost in (("dense", 1.0), ("sg", 1e-6)):
                    seq = getattr(respecialize(
                        eng.program, {s: mode for s in sites}), sec)
                    for ops, _ in compile_steps(seq, eng.impl):
                        pol.table.record(op_label(ops),
                                         op_mode(ops, eng.impl),
                                         bucket, cost)
            auto = eng.infer(targets).embeddings
            rep = eng.dispatch_report()
        assert rep["sources"]["measured"] == rep["decisions"] > 0
        assert rep["sources"]["warmup"] == 0
        forced, _ = serve(graph, cfg, params, ServingConfig(
            batch_size=2, mode="sg"), targets)
        np.testing.assert_array_equal(auto, forced)

    def test_warmup_then_exploit_deterministic(self, graph):
        import jax
        from repro.gnn.model import init_gnn
        cfg = make_cfg(graph)
        params = init_gnn(cfg, jax.random.PRNGKey(3))
        dc = DispatchConfig(warmup_passes=1, seed=11,
                            autotune_blocks=False)
        histories = []
        for _ in range(2):
            with DecoupledEngine(graph, cfg, params=params,
                                 config=ServingConfig(
                                     batch_size=2, mode="auto",
                                     dispatch=dc)) as eng:
                eng.infer(np.arange(8))           # 4 batches
                rep = eng.dispatch_report()
                histories.append(list(eng.dispatch.warmup.history))
        assert histories[0] == histories[1]       # seeded determinism
        # 2 warmup slots (1 pass per side), then measured exploitation
        assert rep["sources"]["warmup"] == 2
        assert rep["sources"]["measured"] == 2
        assert rep["sources"]["flop"] == 0
        assert rep["warmup"]["done"] == {int(2 * N).bit_length(): 2}

    def test_forced_mode_keeps_policy_inert(self, graph):
        cfg = make_cfg(graph)
        with DecoupledEngine(graph, cfg, config=ServingConfig(
                batch_size=2, mode="sg",
                dispatch=DispatchConfig())) as eng:
            eng.infer(np.arange(4))
            rep = eng.dispatch_report()
            assert eng.dispatch is None           # policy never built
        assert rep["policy"] == "forced"
        assert rep["sources"] == {"forced": 2}


# ---------------------------------------------------------------------------
# Pallas block autotune


class TestBlockAutotune:
    def test_best_block_requires_full_grid(self):
        t = CalibrationTable()
        cands = (128, 256, 512)
        assert best_block(t, "fused_gnn", "bf=", cands, 7) is None
        # the tuner records every legal candidate in one pass per
        # bucket, so per-bucket legality == "has a cell at this bucket";
        # cells at OTHER buckets do not leak in
        t.record("fused_gnn", "pallas/bf=128", 7, 2e-3)
        t.record("fused_gnn", "pallas/bf=256", 7, 1e-3)
        t.record("fused_gnn", "pallas/bf=512", 8, 9e-4)
        assert best_block(t, "fused_gnn", "bf=", cands, 7) == 256
        assert best_block(t, "fused_gnn", "bf=", cands, 8) == 512
        t.record("fused_gnn", "pallas/bf=512", 7, 5e-4)
        assert best_block(t, "fused_gnn", "bf=", cands, 7) == 512

    def test_autotune_records_cells_and_policy_consumes(self):
        """run_block_autotune populates (kernel, pallas/b*=) cells for
        every legal candidate; the policy's block overrides appear once
        the grid is complete."""
        import jax
        from repro.core.program import lower_and_specialize
        from repro.gnn.model import init_gnn
        from repro.core.subgraph import build_batch
        from repro.kernels.fused_gnn import BLOCK_F_CANDIDATES
        from repro.kernels.scatter_gather import BLOCK_E_CANDIDATES
        from repro.obs.calib import run_block_autotune, size_bucket
        # the grid holds only blocks Mosaic accepts: whole 128-lane tiles
        assert all(b % 128 == 0 for b in BLOCK_F_CANDIDATES)
        assert all(b % 128 == 0 for b in BLOCK_E_CANDIDATES)
        g = get_graph("flickr", scale=0.005, seed=1)
        cfg = make_cfg(g)
        params = init_gnn(cfg, jax.random.PRNGKey(0))
        prog, _ = lower_and_specialize(cfg, force="dense")
        sb = build_batch(g, [1, 2], N, e_pad=64, num_threads=1)
        with DecoupledEngine(g, cfg, params=params, config=ServingConfig(
                batch_size=2, mode="sg")) as eng:
            batch = eng.device_batch(sb)
        batch.setdefault("adj", sb.adj)
        table = CalibrationTable()
        run_block_autotune(prog, params, batch, table)
        bucket = size_bucket(batch)
        fout = params["layer0"]["w"].shape[1]
        legal_bf = [b for b in BLOCK_F_CANDIDATES
                    if b <= fout and fout % b == 0]
        for b in legal_bf:
            assert table.lookup("fused_gnn", f"pallas/bf={b}",
                                bucket) is not None
        for b in BLOCK_E_CANDIDATES:
            assert table.lookup("scatter_gather", f"pallas/be={b}",
                                bucket) is not None
        pol = DispatchPolicy(prog, "pallas", table, n=N,
                             f_in=cfg.f_in, f_hidden=cfg.f_hidden)
        blocks = pol._blocks(bucket)
        assert legal_bf and blocks.get("block_f") in legal_bf
        assert blocks.get("block_e") in BLOCK_E_CANDIDATES

    def test_exploration_failures_are_counted_not_swallowed(
            self, graph, monkeypatch):
        """A failing autotune pass leaves serving intact, but every
        failure is counted in dispatch_report(), warned, and exported as
        repro_exploration_errors_total."""
        import repro.obs.calib as calib
        from repro.obs.metrics import TelemetryConfig

        def broken(*a, **k):
            raise RuntimeError("block refused")

        monkeypatch.setattr(calib, "run_block_autotune", broken)
        cfg = make_cfg(graph)
        with pytest.warns(RuntimeWarning, match="block refused"):
            with DecoupledEngine(graph, cfg, config=ServingConfig(
                    batch_size=2, mode="auto", impl="pallas",
                    telemetry=TelemetryConfig(),
                    dispatch=DispatchConfig(warmup_passes=1))) as eng:
                out = eng.infer(np.arange(8)).embeddings
                rep = eng.dispatch_report()
                text = eng.metrics_text(cluster=False)
        assert np.isfinite(out).all() and out.shape[0] == 8
        # one autotune pass per warmup batch: 2 slots (1 pass per side)
        assert rep["exploration_errors"] == rep["sources"]["warmup"] == 2
        assert "repro_exploration_errors_total" in text


# ---------------------------------------------------------------------------
# persistence


class TestPersistence:
    def _table(self):
        t = CalibrationTable()
        for i, v in enumerate((1e-4, 2e-4, 3e-4, 5e-3)):
            t.record("Aggregate", "xla/dense", 7, v)
            t.record("Aggregate", "xla/sg", 7, v * 0.1)
        t.passes = 4
        return t

    def test_roundtrip_is_lossless(self, graph, tmp_path):
        cfg = make_cfg(graph)
        t = self._table()
        path = str(tmp_path / "calib")
        save_calibration(path, t, graph=graph, cfg=cfg, impl="xla")
        t2 = load_calibration(path, graph=graph, cfg=cfg, impl="xla")
        assert t2.passes == t.passes and len(t2) == len(t)
        for mode in ("xla/dense", "xla/sg"):
            assert t2.lookup("Aggregate", mode, 7) == \
                t.lookup("Aggregate", mode, 7)    # bit-identical p50s

    def test_stale_artifact_refuses(self, graph, tmp_path):
        cfg = make_cfg(graph)
        path = str(tmp_path / "calib")
        save_calibration(path, self._table(), graph=graph, cfg=cfg,
                         impl="xla")
        other_cfg = GNNConfig(kind="gcn", n_layers=2, receptive_field=N,
                              f_in=graph.feature_dim, f_hidden=256)
        with pytest.raises(CalibrationArtifactError, match="rebuild"):
            load_calibration(path, graph=graph, cfg=other_cfg,
                             impl="xla")
        with pytest.raises(CalibrationArtifactError, match="impl|model"):
            load_calibration(path, graph=graph, cfg=cfg, impl="pallas")
        g2 = sparse_graph()
        with pytest.raises(CalibrationArtifactError,
                           match="graph_fingerprint"):
            load_calibration(path, graph=g2, cfg=make_cfg(g2),
                             impl="xla")

    def test_engine_saves_on_close_and_restarts_warm(self, graph,
                                                     tmp_path):
        import jax
        from repro.ckpt.checkpoint import committed_steps
        from repro.gnn.model import init_gnn
        cfg = make_cfg(graph)
        params = init_gnn(cfg, jax.random.PRNGKey(3))
        path = str(tmp_path / "calib")
        dc = DispatchConfig(warmup_passes=1, autotune_blocks=False,
                            artifact=path)
        sconf = ServingConfig(batch_size=2, mode="auto", dispatch=dc)
        with DecoupledEngine(graph, cfg, params=params,
                             config=sconf) as eng:
            eng.infer(np.arange(8))               # warmup fills the table
            cells = len(eng._calib)
        assert committed_steps(path)              # close() persisted it
        assert cells > 0
        with DecoupledEngine(graph, cfg, params=params,
                             config=sconf) as eng:
            assert len(eng._calib) == cells       # loaded, not rebuilt
            eng.infer(np.arange(4))
            rep = eng.dispatch_report()
        # persisted cells -> measured from the FIRST batch, no warmup
        assert rep["sources"] == {"measured": 2, "flop": 0,
                                  "warmup": 0, "forced": 0}


# ---------------------------------------------------------------------------
# observability surfaces


class TestObservability:
    def test_report_keys_covered_by_schema(self, graph):
        from repro.core.report_schema import SCHEMA
        cfg = make_cfg(graph)
        with DecoupledEngine(graph, cfg, config=ServingConfig(
                batch_size=2, mode="auto",
                dispatch=DispatchConfig(warmup_passes=1,
                                        autotune_blocks=False))) as eng:
            eng.infer(np.arange(4))
            rep = eng.dispatch_report()
        assert rep["enabled"] is True
        assert set(rep) <= set(SCHEMA["dispatch"])
        assert rep["variants"]["size"] <= rep["variants"]["capacity"]

    def test_dispatch_metrics_exposed(self, graph):
        from repro.obs.metrics import TelemetryConfig
        cfg = make_cfg(graph)
        with DecoupledEngine(graph, cfg, config=ServingConfig(
                batch_size=2, mode="auto",
                telemetry=TelemetryConfig(),
                dispatch=DispatchConfig(warmup_passes=1,
                                        autotune_blocks=False))) as eng:
            eng.infer(np.arange(8))
            text = eng.metrics_text(cluster=False)
        assert "repro_dispatch_total" in text
        assert 'source="warmup"' in text
        assert "repro_variant_cache_hits_total" in text
        assert "repro_dispatch_decisions_total" in text

    def test_scheduler_surfaces_batch_edges(self, graph):
        cfg = make_cfg(graph)
        with DecoupledEngine(graph, cfg, config=ServingConfig(
                batch_size=2)) as eng:
            res = eng.infer(np.arange(4))
        s = res.stats.summary()
        assert s["stages"]["batch_edges"] > 0
