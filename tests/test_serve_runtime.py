"""Multi-model streaming serving runtime (the paper's §4.4/§4.5 deployment
shape): persistent streaming scheduler, engine without per-call pipeline
reconstruction, and the multi-tenant GNNServer under one shared DSEPlan."""
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dse import PlanViolation, TPUSpec, explore, plan_covers
from repro.core.engine import DecoupledEngine
from repro.core.scheduler import PipelineScheduler
from repro.gnn.model import GNNConfig
from repro.graphs.synthetic import get_graph
from repro.serve.gnn_server import GNNServer


@pytest.fixture(scope="module")
def graph():
    return get_graph("flickr", scale=0.02, seed=1)   # ~1.8k vertices


def make_engine(graph, kind, batch_size=8, n_layers=2, n=32):
    cfg = GNNConfig(kind=kind, n_layers=n_layers, receptive_field=n,
                    f_in=graph.feature_dim)
    return DecoupledEngine(graph, cfg, batch_size=batch_size)


class TestStreamingScheduler:
    def test_submit_poll_lifecycle(self):
        def host(x):
            return x * 2

        def dev(x):
            return jnp.asarray(x + 1)

        s = PipelineScheduler(host, dev, depth=2)
        tickets = [s.submit(i) for i in range(5)]
        outs = [int(t.result(timeout=10)) for t in tickets]
        assert outs == [2 * i + 1 for i in range(5)]
        assert all(t.done() for t in tickets)
        s.close()

    def test_cumulative_stats_across_calls(self):
        s = PipelineScheduler(lambda x: x, jnp.asarray, depth=2)
        _, call1 = s.run(list(range(3)))
        _, call2 = s.run(list(range(4)))
        assert call1.n_batches == 3 and call2.n_batches == 4
        # cumulative stats keep accumulating over the scheduler lifetime
        assert s.stats.n_batches == 7
        assert len(s.stats.host_times) == 7
        t = s.submit(9)
        t.result(timeout=10)
        assert s.stats.n_batches == 8
        assert s.stats.t_initialization == s.stats.host_times[0]
        s.close()

    def test_bounded_inflight_backpressure(self):
        release = threading.Event()

        def slow_dev(x):
            release.wait(5)
            return jnp.asarray(x)

        s = PipelineScheduler(lambda x: x, slow_dev, depth=1,
                              max_inflight=2)
        t0 = s.submit(0)
        s.submit(1)
        # both slots taken; a third submit must block until one completes
        done = threading.Event()

        def third():
            s.submit(2)
            done.set()

        threading.Thread(target=third, daemon=True).start()
        assert not done.wait(0.2)
        release.set()
        assert done.wait(5)
        t0.result(timeout=10)
        s.flush(timeout=10)
        assert s.stats.n_batches == 3
        s.close()

    def test_host_error_propagates(self):
        def bad_host(x):
            raise RuntimeError("boom")

        s = PipelineScheduler(bad_host, jnp.asarray, depth=2)
        t = s.submit(1)
        with pytest.raises(RuntimeError, match="boom"):
            t.result(timeout=10)
        s.flush(timeout=10)   # pipeline survives the failed batch
        ok = s.submit(2)      # ...but host_fn still raises; error isolated
        with pytest.raises(RuntimeError):
            ok.result(timeout=10)
        s.close()

    def test_on_done_callback_fires(self):
        got = []
        s = PipelineScheduler(lambda x: x, jnp.asarray, depth=2)
        t = s.submit(7, on_done=lambda tk: got.append(int(tk.result())))
        t.result(timeout=10)
        s.flush(timeout=10)
        assert got == [7]
        s.close()


class TestPersistentEngine:
    def test_no_scheduler_reconstruction_per_batch(self, graph):
        eng = make_engine(graph, "gcn")
        sched = eng.scheduler
        r1 = eng.infer(np.arange(20))             # 3 micro-batches
        r2 = eng.infer(np.arange(20, 36))         # 2 micro-batches
        # the SAME scheduler served every micro-batch of both calls
        assert eng.scheduler is sched
        assert sched.stats.n_batches == 5
        assert r1.stats.n_batches == 3 and r2.stats.n_batches == 2
        eng.close()

    def test_streaming_matches_batch(self, graph):
        eng = make_engine(graph, "sage")
        targets = np.arange(8)
        ref = eng.infer(targets, overlap=False).embeddings
        tk = eng.submit_chunk(targets)
        out = np.asarray(tk.result(timeout=60))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
        eng.close()

    def test_tail_padding(self, graph):
        eng = make_engine(graph, "gcn")
        padded = eng.pad_targets(np.array([3, 4, 5]))
        assert padded.shape == (8,)
        assert (padded[3:] == 5).all()
        with pytest.raises(ValueError):
            eng.pad_targets(np.arange(9))
        res = eng.infer(np.arange(11))            # tail chunk of 3
        assert res.embeddings.shape == (11, eng.cfg.f_hidden)
        assert np.isfinite(res.embeddings).all()
        eng.close()


class TestSharedPlan:
    def test_plan_covers(self):
        cfgs = [GNNConfig(kind=k, n_layers=2, receptive_field=64, f_in=128)
                for k in ("gcn", "sage", "gat")]
        plan = explore(cfgs)
        for c in cfgs:
            assert plan_covers(plan, c) == []
        monster = GNNConfig(kind="gcn", n_layers=2, receptive_field=4096,
                            f_in=4096)
        assert plan_covers(plan, monster, TPUSpec()) != []

    def test_register_rejects_model_outside_fixed_plan(self, graph):
        eng = make_engine(graph, "gcn")
        tight = TPUSpec(vmem_bytes=2 ** 10)       # nothing fits 1 KiB
        plan = explore([eng.cfg])
        srv = GNNServer(max_wait_s=0.01, plan=plan, spec=tight)
        with pytest.raises(PlanViolation):
            srv.register("gcn", eng)
        eng.close()


    def test_spec_names_its_chip(self):
        import types
        spec = TPUSpec()
        spec.check_device(types.SimpleNamespace(
            device_kind=spec.device_kind, platform="tpu"))
        with pytest.raises(RuntimeError, match="TPU v4"):
            spec.check_device(types.SimpleNamespace(
                device_kind="TPU v4", platform="tpu"))


class TestMultiModelServer:
    def test_two_kinds_concurrently_match_standalone(self, graph):
        engines = {k: make_engine(graph, k) for k in ("gcn", "sage")}
        srv = GNNServer(max_wait_s=0.01)
        for k, e in engines.items():
            srv.register(k, e)
        assert srv.plan is not None and srv.plan.ops_ok
        srv.start()
        rng = np.random.default_rng(0)
        reqs = []
        for t in rng.integers(0, graph.num_vertices, 32):
            reqs.append(srv.submit(int(t), model="gcn"))
            reqs.append(srv.submit(int(t) % 97, model="sage"))
        srv.drain(reqs, timeout=300)
        srv.stop()
        assert all(r.embedding is not None for r in reqs)
        # routed + micro-batched + padded results == standalone engine.infer
        for kind in ("gcn", "sage"):
            mine = [r for r in reqs if r.model == kind]
            tgts = np.array([r.target for r in mine])
            ref = engines[kind].infer(tgts, overlap=False).embeddings
            got = np.stack([r.embedding for r in mine])
            np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
        rep = srv.report()
        for kind in ("gcn", "sage"):
            m = rep["models"][kind]
            assert m["latency"]["n"] == 32
            assert m["latency"]["p50"] <= m["latency"]["p90"] \
                <= m["latency"]["p99"]
            assert 0.0 <= m["stages"]["overlap"] <= 1.0
        assert rep["plan"]["block_f"] % 128 == 0
        for e in engines.values():
            e.close()

    def test_single_model_backcompat(self, graph):
        eng = make_engine(graph, "gcn")
        srv = GNNServer(eng, max_wait_s=0.01)     # legacy positional form
        srv.start()
        reqs = [srv.submit(i) for i in range(10)]
        srv.drain(reqs, timeout=120)
        srv.stop()
        p = srv.stats.percentiles()
        assert p["n"] == 10 and p["p99"] > 0
        eng.close()

    def test_unknown_model_rejected(self, graph):
        eng = make_engine(graph, "gcn")
        srv = GNNServer(max_wait_s=0.01)
        srv.register("gcn", eng)
        srv.register("gcn2", make_engine(graph, "gcn"))
        with pytest.raises(ValueError):
            srv.submit(0)                          # ambiguous: two models
        with pytest.raises(KeyError):
            srv.submit(0, model="nope")
        with pytest.raises(ValueError):
            srv.register("gcn", eng)               # duplicate name
        eng.close()

    def test_drain_raises_on_failed_batch(self, graph):
        """A host-side failure surfaces through drain() with its cause,
        instead of burning the whole drain timeout."""
        eng = make_engine(graph, "gcn", batch_size=4)
        srv = GNNServer(eng, max_wait_s=0.01)
        srv.start()
        bad = srv.submit(graph.num_vertices + 10**6)   # out-of-range vertex
        with pytest.raises(RuntimeError, match="failed"):
            srv.drain([bad], timeout=120)
        srv.stop()
        eng.close()

    def test_server_restart_serves_again(self, graph):
        """stop() then start() must serve (lane stop flags are cleared)."""
        eng = make_engine(graph, "gcn", batch_size=4)
        srv = GNNServer(eng, max_wait_s=0.01)
        srv.start()
        r1 = [srv.submit(i) for i in range(4)]
        srv.drain(r1, timeout=120)
        srv.stop()
        srv.start()
        r2 = [srv.submit(i) for i in range(4)]
        srv.drain(r2, timeout=120)
        srv.stop()
        np.testing.assert_allclose(np.stack([r.embedding for r in r1]),
                                   np.stack([r.embedding for r in r2]),
                                   rtol=1e-6)
        eng.close()

    def test_partial_tail_batch_padded_per_lane(self, graph):
        """Requests that don't fill C still come back correct (the lane
        pads the tail micro-batch with repeated targets)."""
        eng = make_engine(graph, "gcn", batch_size=8)
        srv = GNNServer(max_wait_s=0.01)
        srv.register("gcn", eng)
        srv.start()
        reqs = [srv.submit(i, model="gcn") for i in (5, 6, 7)]  # 3 < C=8
        srv.drain(reqs, timeout=120)
        srv.stop()
        ref = eng.infer(np.array([5, 6, 7]), overlap=False).embeddings
        got = np.stack([r.embedding for r in reqs])
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
        time.sleep(0)   # lanes joined in stop(); nothing left in flight
        assert srv.model_stats("gcn").n_batches >= 1
        eng.close()
