"""Sharded feature store: cross-shard gather correctness (bitwise vs the
unsharded resident store), placement policies, uneven budgets, online
PPR-mass repin(), and the per-shard observability surfaced through
SchedulerStats / GNNServer.report()."""
import numpy as np
import pytest

from repro.core.engine import DecoupledEngine
from repro.gnn.model import GNNConfig
from repro.graphs.synthetic import get_graph, zipf_traffic
from repro.store import ShardedFeatureStore, StorePolicy

TARGETS = np.arange(24)


@pytest.fixture(scope="module")
def graph():
    return get_graph("flickr", scale=0.005, seed=1)   # ~450 vertices


@pytest.fixture(scope="module")
def cfg(graph):
    return GNNConfig(kind="gcn", n_layers=2, receptive_field=32,
                     f_in=graph.feature_dim)


@pytest.fixture(scope="module")
def baseline(graph, cfg):
    """Unsharded full-resident store — the bitwise reference."""
    eng = DecoupledEngine(graph, cfg, batch_size=8,
                          store=StorePolicy(features="resident"))
    emb = eng.infer(TARGETS, overlap=False).embeddings
    yield eng, emb
    eng.close()


def _sharded(graph, cfg, params, **kw):
    kw.setdefault("num_shards", 2)
    return DecoupledEngine(graph, cfg, params=params, batch_size=8,
                           store=StorePolicy(features="sharded", **kw))


class TestPolicyValidation:
    def test_sharded_needs_num_shards(self):
        with pytest.raises(ValueError, match="num_shards"):
            StorePolicy(features="sharded")

    def test_shard_knobs_need_sharded(self):
        with pytest.raises(ValueError, match="sharded"):
            StorePolicy(num_shards=2)
        with pytest.raises(ValueError, match="sharded"):
            StorePolicy(features="resident", shard_budget_bytes=1024)

    def test_bad_placement_rejected(self):
        with pytest.raises(ValueError, match="placement"):
            StorePolicy(features="sharded", num_shards=2,
                        placement="rendezvous")

    def test_describe_includes_shard_fields(self):
        p = StorePolicy(features="sharded", num_shards=4,
                        placement="range", shard_budget_bytes=(1, 2, 3, 4))
        d = p.describe()
        assert d["num_shards"] == 4 and d["placement"] == "range"
        assert d["shard_budget_bytes"] == [1, 2, 3, 4]


class TestShardDevices:
    def test_cpu_simulates_missing_devices(self):
        import jax
        from repro.distributed.sharding import shard_devices
        n = len(jax.devices()) + 2
        devs = shard_devices(n)
        assert len(devs) == n and len(set(devs)) < n

    def test_accelerator_shortfall_raises(self, monkeypatch):
        """On a chip, 4 shards on 1 device would silently give up the
        per-device capacity the shards exist for."""
        import types

        import jax
        from repro.distributed.sharding import shard_devices
        chip = types.SimpleNamespace(platform="tpu")
        monkeypatch.setattr(jax, "devices", lambda: [chip])
        with pytest.raises(ValueError, match="4 feature-store shards"):
            shard_devices(4)
        assert shard_devices(1) == [chip]


class TestCrossShardGather:
    @pytest.mark.parametrize("placement", ["hash", "range"])
    @pytest.mark.parametrize("num_shards", [2, 4])
    def test_bitwise_equal_to_unsharded(self, graph, cfg, baseline,
                                        placement, num_shards):
        """Acceptance: sharded (2+ shards) == unsharded resident store,
        bitwise, for both placement policies."""
        ref, emb0 = baseline
        eng = _sharded(graph, cfg, ref.params, num_shards=num_shards,
                       placement=placement)
        emb = eng.infer(TARGETS, overlap=False).embeddings
        np.testing.assert_array_equal(emb, emb0)
        rep = eng.store_report()["features"]
        assert rep["resident_fraction"] == 1.0    # union covers the matrix
        assert rep["miss_rows_shipped"] == 0
        # 2+ shards genuinely split the table and the gather crossed them
        assert min(rep["shard_rows"]) > 0
        assert rep["cross_shard_rows"] > 0
        eng.close()

    def test_uneven_budgets_with_miss_partition(self, graph, cfg,
                                                baseline):
        """Per-shard budgets below the matrix: cold rows fall back to the
        host miss partition, results still bitwise-equal."""
        ref, emb0 = baseline
        row = graph.feature_dim * 4
        eng = _sharded(graph, cfg, ref.params, placement="range",
                       shard_budget_bytes=(96 * row, 32 * row))
        emb = eng.infer(TARGETS, overlap=False).embeddings
        np.testing.assert_array_equal(emb, emb0)
        rep = eng.store_report()["features"]
        assert rep["shard_rows"] == [96, 32]      # uneven split honored
        assert 0 < rep["resident_fraction"] < 1.0
        assert rep["miss_rows_shipped"] > 0       # host fallback exercised
        eng.close()

    def test_miss_block_ships_at_f_in(self, graph, cfg):
        """The miss block crosses the link at f_in: MXU pad columns are a
        resident-table layout concern, never shipped (and never counted
        in bytes_shipped) per batch."""
        from repro.core.ini import ini_batch
        row = graph.feature_dim * 4
        store = ShardedFeatureStore(graph, f_pad=512, num_shards=2,
                                    budget_bytes=16 * row)
        nls = ini_batch(graph, [0, 1], 32, num_threads=1)
        payload, _ = store.host_payload(nls, 32)
        assert payload["miss_feats"].shape[1] == graph.feature_dim  # 500
        # device side pads back to f_pad and reorders correctly
        feats = np.asarray(store.device_feats(payload))
        assert feats.shape == (2, 32, 512)
        np.testing.assert_array_equal(feats[0, 0, :graph.feature_dim],
                                      graph.features[nls[0][0]])
        np.testing.assert_array_equal(feats[..., graph.feature_dim:], 0.0)

    def test_single_shard_degenerates_to_resident(self, graph, cfg,
                                                  baseline):
        ref, emb0 = baseline
        eng = _sharded(graph, cfg, ref.params, num_shards=1)
        emb = eng.infer(TARGETS, overlap=False).embeddings
        np.testing.assert_array_equal(emb, emb0)
        assert eng.store_report()["features"]["cross_shard_rows"] == 0
        eng.close()


class TestRepin:
    def test_repin_promotes_hot_rows_and_stays_bitwise(self, graph, cfg,
                                                       baseline):
        """Online rebalance: after Zipf traffic, repin() promotes the
        observed-hot rows into residency; inference stays bitwise-equal
        and the hit rate does not regress."""
        ref, emb0 = baseline
        row = graph.feature_dim * 4
        # budget small enough that initial (degree-ranked) residency
        # misses part of the traffic
        eng = _sharded(graph, cfg, ref.params, placement="hash",
                       shard_budget_bytes=64 * row)
        traffic = zipf_traffic(graph, 128, a=1.1, seed=2)
        eng.infer(traffic, overlap=False)          # accumulate PPR mass
        st = eng._fsource
        lk0, res0 = st.lookups, st.resident_lookups
        report = eng.repin()
        assert report["promoted"] >= 0 and "mass_balance_after" in report
        assert st.report()["repins"] == 1
        emb = eng.infer(TARGETS, overlap=False).embeddings
        np.testing.assert_array_equal(emb, emb0)   # placement-invariant
        # replay the same traffic: observed-mass residency must serve it
        # at least as well as the degree prior did
        lk1, res1 = st.lookups, st.resident_lookups
        eng.infer(traffic, overlap=False)
        before = res0 / lk0
        after = (st.resident_lookups - res1) / (st.lookups - lk1)
        assert after >= before - 1e-9
        eng.close()

    def test_repin_requires_repinnable_store(self, graph, cfg, baseline):
        ref, _ = baseline    # resident, unsharded: repin now SUPPORTED
        rep = ref.repin()    # (PPR-mass accounting landed on the
        assert rep["resident_rows"] >= 0      # single-device store too)
        eng = DecoupledEngine(graph, cfg, params=ref.params,
                              batch_size=8)   # dense: nothing resident
        with pytest.raises(ValueError, match="repin"):
            eng.repin()
        eng.close()

    def test_inflight_placement_snapshot_survives_repin(self, graph, cfg,
                                                        baseline):
        """A payload prepared before repin() gathers against ITS placement
        generation, not the new one."""
        ref, emb0 = baseline
        eng = _sharded(graph, cfg, ref.params, num_shards=2)
        node_lists, _, _ = eng._node_lists([int(t) for t in TARGETS[:8]])
        payload, _ = eng._fsource.host_payload(node_lists, 32)
        eng.infer(zipf_traffic(graph, 64, a=1.1, seed=3), overlap=False)
        eng.repin()                                # new generation
        feats = np.asarray(eng._fsource.device_feats(payload))
        want = np.zeros_like(feats)
        for i, nl in enumerate(node_lists):
            k = min(len(nl), 32)
            want[i, :k, :graph.feature_dim] = graph.features[nl[:k]]
        np.testing.assert_array_equal(feats, want)
        eng.close()


class TestShardObservability:
    def test_scheduler_accumulates_per_shard_bytes(self, graph, cfg,
                                                   baseline):
        ref, _ = baseline
        eng = _sharded(graph, cfg, ref.params, num_shards=2)
        eng.infer(TARGETS, overlap=False)
        s = eng.scheduler.stats
        assert len(s.shard_bytes) == 2 and all(b > 0 for b in s.shard_bytes)
        assert s.shard_balance >= 1.0
        assert s.summary()["shards"]["balance"] >= 1.0
        # index-only: per-shard bytes are a small fraction of dense
        assert sum(s.shard_bytes) < s.bytes_dense
        eng.close()

    def test_server_report_surfaces_shard_stats(self, graph, cfg):
        from repro.serve.gnn_server import GNNServer
        eng = DecoupledEngine(graph, cfg, batch_size=4,
                              store=StorePolicy(features="sharded",
                                                num_shards=2,
                                                nbr_cache="lru"))
        srv = GNNServer(eng, max_wait_s=0.005)
        srv.start()
        reqs = [srv.submit(int(t)) for t in [0, 1, 2, 3, 0, 1, 2, 3]]
        srv.drain(reqs, timeout=120)
        srv.stop()
        m = srv.report()["models"]["default"]
        assert len(m["shards"]["bytes"]) == 2
        assert m["shards"]["balance"] >= 1.0
        st = m["store"]["features"]
        assert st["strategy"] == "sharded" and st["num_shards"] == 2
        for key in ("shard_rows", "shard_lookups", "mass_balance",
                    "cross_shard_rows", "placement", "simulated"):
            assert key in st
        eng.close()

    def test_graph_update_refreshes_shard_rows(self, graph, cfg):
        """Feature half of the update hook, sharded edition: mutated rows
        re-upload into their shard tables."""
        import copy
        g = copy.deepcopy(graph)
        eng = DecoupledEngine(g, cfg, batch_size=8,
                              store=StorePolicy(features="sharded",
                                                num_shards=2,
                                                nbr_cache="lru"))
        t = np.arange(8)
        before = eng.infer(t, overlap=False).embeddings
        g.features[:8] += 1.0
        eng.invalidate(np.arange(8))
        after = eng.infer(t, overlap=False).embeddings
        assert np.abs(after - before).max() > 0
        fresh = DecoupledEngine(g, cfg, params=eng.params, batch_size=8)
        np.testing.assert_allclose(
            after, fresh.infer(t, overlap=False).embeddings,
            rtol=1e-6, atol=1e-6)
        fresh.close()
        eng.close()
