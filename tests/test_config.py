"""ServingConfig — the unified serving surface: validation, the
deprecated per-kwarg adapter (``from_kwargs``), the removal of
``dedup_features=``, config-first engine/server construction, and the
versioned report schema every surface now emits."""
import dataclasses

import numpy as np
import pytest

from repro.core.config import ServingConfig
from repro.core.engine import DecoupledEngine
from repro.core.report_schema import SCHEMA, SCHEMA_VERSION
from repro.gnn.model import GNNConfig
from repro.graphs.synthetic import get_graph
from repro.serve.gnn_server import GNNServer
from repro.store import StorePolicy


@pytest.fixture(scope="module")
def graph():
    return get_graph("flickr", scale=0.005, seed=1)   # ~450 vertices


@pytest.fixture(scope="module")
def cfg(graph):
    return GNNConfig(kind="gcn", n_layers=2, receptive_field=16,
                     f_in=graph.feature_dim)


class TestValidation:
    def test_defaults_are_local(self):
        c = ServingConfig()
        assert c.transport == "local" and not c.remote
        assert c.batch_size == 64 and c.depth == 3
        assert isinstance(c.store, StorePolicy)

    def test_frozen(self):
        with pytest.raises(dataclasses.FrozenInstanceError):
            ServingConfig().batch_size = 1

    def test_socket_needs_endpoints(self):
        with pytest.raises(ValueError, match="endpoints"):
            ServingConfig(transport="socket")

    def test_endpoints_need_socket(self):
        with pytest.raises(ValueError, match="transport='socket'"):
            ServingConfig(endpoints=("h:1",))
        with pytest.raises(ValueError, match="transport='socket'"):
            ServingConfig(transport="inproc", endpoints=("h:1",))

    def test_endpoints_list_coerced_to_tuple(self):
        c = ServingConfig(transport="socket", endpoints=["a:1", "b:2"])
        assert c.endpoints == ("a:1", "b:2") and c.remote

    @pytest.mark.parametrize("bad", [
        dict(transport="grpc"), dict(routing="random"),
        dict(batch_size=0), dict(depth=0), dict(num_threads=0),
        dict(max_inflight=0), dict(max_wait_s=-1.0),
        dict(rpc_timeout_s=0.0), dict(rpc_retries=-1),
        dict(rpc_concurrency=0), dict(store="resident"),
    ])
    def test_bad_values_rejected(self, bad):
        with pytest.raises((ValueError, TypeError)):
            ServingConfig(**bad)

    def test_describe_covers_transport(self):
        c = ServingConfig(transport="socket", endpoints=("h:1",),
                          routing="affine")
        d = c.describe()
        assert d["transport"] == "socket"
        assert d["endpoints"] == ["h:1"] and d["routing"] == "affine"
        assert "endpoints" not in ServingConfig().describe()


class TestFromKwargs:
    def test_legacy_kwargs_warn_and_map(self):
        with pytest.warns(DeprecationWarning, match="ServingConfig"):
            c = ServingConfig.from_kwargs(batch_size=8, impl="xla",
                                          num_threads=2)
        assert c.batch_size == 8 and c.num_threads == 2

    def test_unknown_kwarg_lists_valid_options(self):
        with pytest.raises(TypeError, match="unknown serving option"):
            ServingConfig.from_kwargs(batch_sise=8, _warn=False)

    def test_dedup_features_removed_names_replacement(self):
        with pytest.raises(TypeError,
                           match="dedup_features.*packed"):
            ServingConfig.from_kwargs(dedup_features=True, _warn=False)

    def test_base_overlay(self):
        base = ServingConfig(batch_size=16, depth=2)
        c = ServingConfig.from_kwargs(base=base, num_threads=3,
                                      _warn=False)
        assert (c.batch_size, c.depth, c.num_threads) == (16, 2, 3)
        assert ServingConfig.from_kwargs(base=base) is base

    def test_legacy_store_none_means_default(self):
        c = ServingConfig.from_kwargs(store=None, batch_size=4,
                                      _warn=False)
        assert isinstance(c.store, StorePolicy)


class TestEngineConstruction:
    def test_config_first_engine(self, graph, cfg):
        with DecoupledEngine(graph, cfg, config=ServingConfig(
                batch_size=4, num_threads=2)) as eng:
            assert eng.batch_size == 4 and eng.config.num_threads == 2
            out = eng.infer(np.arange(8), overlap=False).embeddings
            assert out.shape == (8, cfg.f_hidden)
            assert np.isfinite(out).all()

    def test_legacy_kwargs_still_work_with_warning(self, graph, cfg):
        with pytest.warns(DeprecationWarning):
            eng = DecoupledEngine(graph, cfg, batch_size=4,
                                  num_threads=2)
        assert eng.config.batch_size == 4
        eng.close()

    def test_legacy_kwargs_overlay_config(self, graph, cfg):
        base = ServingConfig(num_threads=2, depth=2)
        with pytest.warns(DeprecationWarning):
            eng = DecoupledEngine(graph, cfg, config=base, batch_size=4)
        assert eng.config.batch_size == 4
        assert eng.config.depth == 2          # base survives the overlay
        eng.close()

    def test_dedup_features_removed_from_engine(self, graph, cfg):
        with pytest.raises(TypeError, match="dedup_features.*packed"):
            DecoupledEngine(graph, cfg, dedup_features=True)

    def test_server_builds_engine_from_config(self, graph, cfg):
        srv = GNNServer(max_wait_s=0.005)
        srv.register("gcn", graph=graph, cfg=cfg,
                     config=ServingConfig(batch_size=4, num_threads=2))
        eng = srv.engine_for("gcn")
        assert eng.batch_size == 4
        srv.start()
        reqs = [srv.submit(i) for i in range(4)]
        srv.drain(reqs, timeout=120)
        srv.stop()
        assert all(r.embedding is not None for r in reqs)
        eng.close()

    def test_register_rejects_config_with_engine(self, graph, cfg):
        with DecoupledEngine(graph, cfg, config=ServingConfig(
                batch_size=4)) as eng:
            srv = GNNServer(max_wait_s=0.005)
            with pytest.raises(TypeError, match="config="):
                srv.register("gcn", eng, config=ServingConfig())
            with pytest.raises(TypeError, match="graph="):
                srv.register("gcn")


class TestReportSchema:
    def test_summary_is_versioned_and_nested(self, graph, cfg):
        with DecoupledEngine(graph, cfg, config=ServingConfig(
                batch_size=4, num_threads=2)) as eng:
            res = eng.infer(np.arange(8))
            s = res.stats.summary()
            assert s["schema_version"] == SCHEMA_VERSION
            for key in ("t_wall", "t_host", "t_device", "t_init"):
                assert key in s["latency"]
            assert set(s["stages"]) == {"times", "waits", "overlap",
                                        "batches", "build_hit_rate",
                                        "batch_edges"}
            for key in ("bytes_shipped", "bytes_dense", "transfer_ratio",
                        "cache_hit_rate", "dedup_ratio"):
                assert key in s["store"]
            # local deployment: no transport, no shards -> sections absent
            assert "rpc" not in s and "shards" not in s
            # every emitted key is documented in the schema contract
            for section, keys in s.items():
                if section == "schema_version":
                    continue
                assert section in SCHEMA
                for k in keys:
                    assert k in SCHEMA[section], (section, k)

    def test_server_report_is_versioned(self, graph, cfg):
        srv = GNNServer(max_wait_s=0.005)
        srv.register("gcn", graph=graph, cfg=cfg,
                     config=ServingConfig(batch_size=4, num_threads=2))
        srv.start()
        srv.drain([srv.submit(i) for i in range(4)], timeout=120)
        srv.stop()
        rep = srv.report()
        assert rep["schema_version"] == SCHEMA_VERSION
        m = rep["models"]["gcn"]
        for section in ("latency", "stages", "store", "ack"):
            assert section in m
        assert m["latency"]["n"] == 4
        assert "policy" in m["store"] and "features" in m["store"]
        assert rep["aggregate"]["latency"]["n"] == 4
        srv.engine_for("gcn").close()


class TestCompileCache:
    """Entry points place JAX's persistent compile cache through one
    helper; the library itself never turns it on."""

    @pytest.fixture
    def updates(self, monkeypatch):
        import jax
        calls = {}
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: calls.__setitem__(k, v))
        return calls

    def test_env_dir_is_used_and_no_other_set(self, updates, monkeypatch,
                                              tmp_path):
        from repro.compile_cache import enable_compile_cache
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
        assert enable_compile_cache(str(tmp_path)) == str(tmp_path / "c")
        assert "jax_compilation_cache_dir" not in updates

    def test_fallback_is_fixed_path_in_checkout(self, updates, monkeypatch,
                                               tmp_path):
        from repro.compile_cache import enable_compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = enable_compile_cache(str(tmp_path))
        assert path == str(tmp_path / ".jax_cache")
        assert updates["jax_compilation_cache_dir"] == path
        assert enable_compile_cache(str(tmp_path)) == path    # stable

    def test_library_import_leaves_cache_alone(self):
        import os

        import jax
        import repro.serve.gnn_server  # noqa: F401
        assert jax.config.jax_compilation_cache_dir == \
            os.environ.get("JAX_COMPILATION_CACHE_DIR")
