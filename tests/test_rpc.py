"""Multi-host graph serving: wire codec exactness (round-trip, corrupt /
truncated / cross-version frames), remote Select/Build bitwise equality
against the in-process pipeline over both transports (loopback and a real
TCP socket — including a separate graph-host PROCESS), per-ticket timeout
+ bounded retry semantics, and the kill-a-graph-host degradation path."""
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import repro
from repro.core.config import ServingConfig
from repro.core.engine import DecoupledEngine
from repro.distributed import wire
from repro.distributed.graph_host import GraphHostService
from repro.distributed.rpc import (GraphHostServer, HostPool,
                                   InProcTransport, RemoteCallError,
                                   RPCTimeout, SocketTransport,
                                   TransportError)
from repro.gnn.model import GNNConfig
from repro.graphs.synthetic import get_graph
from repro.store import StorePolicy

N = 16
C = 4
SCALE = 0.004            # ~357 vertices
SEED = 1
TARGETS = np.arange(12)


@pytest.fixture(scope="module")
def graph():
    return get_graph("flickr", scale=SCALE, seed=SEED)


def _cfg(kind, graph):
    return GNNConfig(kind=kind, n_layers=2, receptive_field=N,
                     f_in=graph.feature_dim)


def _subproc_env():
    env = dict(os.environ)
    src = os.path.dirname(os.path.dirname(os.path.abspath(repro.__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    return env


def _spawn_graph_host(extra_args=()):
    """Launch a graph host subprocess serving the SAME synthetic graph
    (dataset+scale+seed pin it bitwise) and return (proc, endpoint)."""
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.distributed.graph_host",
         "--dataset", "flickr", "--scale", str(SCALE),
         "--seed", str(SEED), "--port", "0", "--num-threads", "2",
         *extra_args],
        env=_subproc_env(), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    t0 = time.time()
    while True:
        line = proc.stdout.readline()
        if line.startswith("GRAPH_HOST_LISTENING"):
            _, host, port = line.split()
            return proc, f"{host}:{port}"
        if proc.poll() is not None or time.time() - t0 > 60:
            proc.kill()
            raise RuntimeError(f"graph host failed to start: {line!r}")


class TestWireCodec:
    def test_roundtrip_every_dtype_and_shape(self):
        rng = np.random.default_rng(0)
        arrays = [
            np.asarray(7, np.int32),                       # 0-d scalar
            np.empty((0, 3), np.float32),                  # empty
            rng.integers(-9, 9, (5,), endpoint=True).astype(np.int8),
            rng.integers(0, 2**31, (3, 4)).astype(np.int64),
            rng.standard_normal((2, 3, 4)).astype(np.float32),
            rng.standard_normal((8,)).astype(np.float64),
            np.array([True, False, True]),
        ]
        tree = {"arrays": arrays, "s": "x", "i": 3, "f": 0.5,
                "none": None, "flag": True, "nested": {"a": arrays[4]},
                "blob": b"\x00\xffraw"}
        out = wire.decode(wire.encode(tree))
        for a, b in zip(arrays, out["arrays"]):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(a, b)
        assert out["s"] == "x" and out["i"] == 3 and out["f"] == 0.5
        assert out["none"] is None and out["flag"] is True
        assert out["blob"] == b"\x00\xffraw"
        np.testing.assert_array_equal(out["nested"]["a"], arrays[4])

    def test_batchplan_roundtrip_exact(self, graph):
        """Full BatchPlan — node lists, frontiers, rows, device payload
        with the store's generation pin — survives the wire bitwise."""
        cfg = _cfg("gcn", graph)
        with DecoupledEngine(graph, cfg, config=ServingConfig(
                batch_size=C,
                store=StorePolicy(features="resident",
                                  nbr_cache="lru"))) as eng:
            plan = eng.plan(TARGETS[:C])
            out = wire.plan_from_wire(
                wire.decode(wire.encode(wire.plan_to_wire(plan))))
            np.testing.assert_array_equal(out.targets, plan.targets)
            assert len(out.node_lists) == len(plan.node_lists)
            for a, b in zip(plan.node_lists, out.node_lists):
                assert a.dtype == b.dtype
                np.testing.assert_array_equal(a, b)
            for t, fr in plan.frontiers.items():
                np.testing.assert_array_equal(out.frontiers[t], fr)
            for a, b in zip(plan.rows, out.rows):
                for f in ("adj", "adj_mean", "mask", "edge_src",
                          "edge_dst", "edge_w", "self_w", "edge_w_mean"):
                    ax, bx = getattr(a, f), getattr(b, f)
                    assert ax.dtype == bx.dtype
                    np.testing.assert_array_equal(ax, bx)
            assert set(out.device) == set(plan.device)
            for k in plan.device:
                a, b = np.asarray(plan.device[k]), out.device[k]
                assert a.dtype == b.dtype and a.shape == b.shape
                np.testing.assert_array_equal(a, b)
            # generation pin survives the hop (resident store)
            assert int(out.device["store_gen"]) \
                == int(plan.device["store_gen"])
            eng.run_device(plan)     # consume the pinned generation

    def test_sharded_payload_roundtrip_exact(self, graph):
        cfg = _cfg("gcn", graph)
        with DecoupledEngine(graph, cfg, config=ServingConfig(
                batch_size=C,
                store=StorePolicy(features="sharded",
                                  num_shards=2))) as eng:
            plan = eng.plan(TARGETS[:C])
            out = wire.decode(wire.encode(
                {k: np.asarray(v) for k, v in plan.device.items()}))
            for k, v in plan.device.items():
                a = np.asarray(v)
                assert a.dtype == out[k].dtype and a.shape == out[k].shape
                np.testing.assert_array_equal(a, out[k])
            assert int(out["shard_gen"]) == int(plan.device["shard_gen"])
            eng.run_device(plan)

    def test_truncated_frame_rejected(self):
        frame = wire.encode({"a": np.arange(100)})
        with pytest.raises(wire.WireFormatError, match="truncated"):
            wire.decode(frame[:-10])
        with pytest.raises(wire.WireFormatError, match="header"):
            wire.decode(frame[:6])

    def test_corrupt_magic_rejected(self):
        frame = bytearray(wire.encode({"a": 1}))
        frame[:4] = b"EVIL"
        with pytest.raises(wire.WireFormatError, match="magic"):
            wire.decode(bytes(frame))

    def test_version_mismatch_actionable(self):
        frame = bytearray(wire.encode({"a": 1}))
        frame[4:6] = (99).to_bytes(2, "big")
        with pytest.raises(wire.WireVersionError,
                           match="v99.*v1|upgrade"):
            wire.decode(bytes(frame))

    def test_unencodable_value_rejected(self):
        with pytest.raises(wire.WireFormatError, match="cannot encode"):
            wire.encode({"bad": object()})


class TestRemoteBitwise:
    @pytest.mark.parametrize("kind", ["gcn", "sage", "gat"])
    def test_inproc_loopback_matches_local(self, graph, kind):
        """Remote Select/Build over the loopback transport (full codec
        both legs) is bitwise-identical to the in-process pipeline."""
        cfg = _cfg(kind, graph)
        with DecoupledEngine(graph, cfg, config=ServingConfig(
                batch_size=C, num_threads=2)) as local:
            ref = local.infer(TARGETS).embeddings
            with DecoupledEngine(
                    graph, cfg, params=local.params,
                    config=ServingConfig(batch_size=C, num_threads=2,
                                         transport="inproc")) as remote:
                got = remote.infer(TARGETS).embeddings
                np.testing.assert_array_equal(got, ref)
                s = remote.scheduler.stats
                assert s.rpc_calls == len(TARGETS) // C
                assert s.rpc_bytes_out > 0 and s.rpc_bytes_in > 0
                assert s.rpc_errors == 0
                rpc = s.summary()["rpc"]
                assert rpc["calls"] == s.rpc_calls

    def test_socket_transport_in_thread_matches_local(self, graph):
        """SocketTransport against a threaded server in this process:
        real TCP framing, bitwise-equal outputs, rpc.* counters."""
        cfg = _cfg("gcn", graph)
        svc = GraphHostService(graph, num_threads=2)
        server = GraphHostServer(svc)
        try:
            sc = ServingConfig(batch_size=C, num_threads=2,
                               transport="socket",
                               endpoints=(server.endpoint,),
                               rpc_timeout_s=60.0)
            with DecoupledEngine(graph, cfg, config=ServingConfig(
                    batch_size=C, num_threads=2)) as local:
                ref = local.infer(TARGETS).embeddings
                with DecoupledEngine(graph, cfg, params=local.params,
                                     config=sc) as remote:
                    got = remote.infer(TARGETS).embeddings
                    np.testing.assert_array_equal(got, ref)
                    rep = remote.store_report()
                    hosts = rep["graph_hosts"]
                    assert hosts[0]["healthy"]
                    assert hosts[0]["report"]["requests"] >= 3
                    # remote invalidation drops the graph host's caches
                    assert remote.invalidate(TARGETS[:2]) > 0
        finally:
            server.close()

    def test_two_process_socket_matches_local(self, graph):
        """The real thing: a graph host in a SEPARATE process serves
        Select/Build over TCP; outputs match in-process bitwise."""
        cfg = _cfg("gcn", graph)
        proc, endpoint = _spawn_graph_host()
        try:
            with DecoupledEngine(graph, cfg, config=ServingConfig(
                    batch_size=C, num_threads=2)) as local:
                ref = local.infer(TARGETS).embeddings
                with DecoupledEngine(
                        graph, cfg, params=local.params,
                        config=ServingConfig(
                            batch_size=C, num_threads=2,
                            transport="socket",
                            endpoints=(endpoint,),
                            rpc_timeout_s=120.0)) as remote:
                    got = remote.infer(TARGETS).embeddings
                    np.testing.assert_array_equal(got, ref)
        finally:
            proc.kill()
            proc.wait(timeout=10)

    def test_graph_host_process_pins_cpu(self):
        """A graph host never claims the accelerator its parent device
        host holds: main() pins JAX to the CPU platform before any
        backend starts, whatever JAX_PLATFORMS the parent passed on."""
        env = _subproc_env()
        env.pop("JAX_PLATFORMS", None)
        code = ("import jax\n"
                "from repro.distributed import graph_host\n"
                "try:\n"
                "    graph_host.main(['--help'])\n"
                "except SystemExit:\n"
                "    pass\n"
                "print(jax.config.jax_platforms)\n")
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip().splitlines()[-1] == "cpu"


class TestFailureIsolation:
    def test_kill_graph_host_errors_only_inflight_tickets(self, graph):
        """Two graph hosts, no retries: killing one mid-stream errors
        the tickets in flight on it (TransportError), the pool marks it
        down, and every later ticket lands on the survivor — the
        pipeline degrades instead of wedging."""
        cfg = _cfg("gcn", graph)
        proc_a, ep_a = _spawn_graph_host()
        proc_b, ep_b = _spawn_graph_host()
        eng = DecoupledEngine(graph, cfg, config=ServingConfig(
            batch_size=C, num_threads=2, transport="socket",
            endpoints=(ep_a, ep_b), rpc_retries=0, rpc_timeout_s=120.0,
            rpc_concurrency=1))
        try:
            # warm both hosts (round-robin touches each)
            for i in range(2):
                eng.submit_chunk(TARGETS[:C]).result(timeout=120)
            proc_a.kill()
            proc_a.wait(timeout=10)
            tickets = [eng.submit_chunk(TARGETS[:C]) for _ in range(6)]
            outcomes = []
            for t in tickets:
                try:
                    t.result(timeout=120)
                    outcomes.append("ok")
                except TransportError:
                    outcomes.append("err")
            # the dead host fails SOME tickets (those routed to it before
            # quarantine kicks in) but never all: the survivor serves the
            # rest, and the scheduler stays alive for new submissions
            assert "err" in outcomes and "ok" in outcomes
            assert eng.scheduler.stats.rpc_errors >= 1
            after = eng.submit_chunk(TARGETS[:C]).result(timeout=120)
            assert np.isfinite(np.asarray(after)).all()
            healthy = {h["endpoint"]: h["healthy"]
                       for h in eng._host_pool.report()}
            assert healthy[ep_b]
        finally:
            eng.close()
            for p in (proc_a, proc_b):
                p.kill()
                p.wait(timeout=10)

    def test_retry_reroutes_to_healthy_host(self, graph):
        """With retries enabled, a dead host costs a retry, not a
        ticket: calls transparently fail over to the live host."""
        cfg = _cfg("gcn", graph)
        proc, endpoint = _spawn_graph_host()
        # a dead endpoint: bind+close to get a port nothing listens on
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        dead = f"127.0.0.1:{s.getsockname()[1]}"
        s.close()
        eng = DecoupledEngine(graph, cfg, config=ServingConfig(
            batch_size=C, num_threads=2, transport="socket",
            endpoints=(dead, endpoint), rpc_retries=1,
            rpc_timeout_s=120.0))
        try:
            out = eng.infer(TARGETS).embeddings
            assert np.isfinite(out).all()
            assert eng.scheduler.stats.rpc_errors == 0
        finally:
            eng.close()
            proc.kill()
            proc.wait(timeout=10)

    def test_per_call_timeout_raises_rpc_timeout(self):
        """A hung handler trips the per-call deadline as RPCTimeout (a
        TransportError — retryable), and the pool quarantines the
        host."""
        class Stuck:
            def handle(self, request):
                time.sleep(2.0)
                return {"ok": True, "result": None, "remote_s": 2.0}

        server = GraphHostServer(Stuck())
        pool = HostPool([SocketTransport(server.endpoint)],
                        timeout=0.2, retries=0)
        try:
            with pytest.raises(RPCTimeout, match="within 0.2s"):
                pool.call("select_build", {"x": 1})
            assert not pool.report()[0]["healthy"]
        finally:
            pool.close()
            server.close()

    def test_remote_application_error_not_retried(self, graph):
        """A handler exception is a RemoteCallError carrying the remote
        type/message — deterministic, so the pool must NOT burn retries
        on other hosts."""
        svc = GraphHostService(graph, num_threads=1)
        calls = []

        class Counting(InProcTransport):
            def call(self, method, payload, timeout=None):
                calls.append(method)
                return super().call(method, payload, timeout)

        pool = HostPool([Counting(svc), Counting(svc)], retries=2)
        with pytest.raises(RemoteCallError, match="KeyError|missing"):
            pool.call("select_build", {"targets": np.arange(2)})
        assert len(calls) == 1          # no retry
        with pytest.raises(RemoteCallError, match="unknown method"):
            pool.call("no_such_method", None)
        svc.close()

    def test_affine_routing_pins_targets_to_hosts(self, graph):
        svc_a = GraphHostService(graph, num_threads=1)
        svc_b = GraphHostService(graph, num_threads=1)
        pool = HostPool([InProcTransport(svc_a), InProcTransport(svc_b)],
                        routing="affine")
        payload = {"targets": np.asarray([2], np.int64), "n": N,
                   "alpha": 0.15, "eps": 1e-4, "e_pad": 64}
        for _ in range(3):              # affinity 2 -> host index 0
            pool.call("select_build", payload, affinity=2)
        assert svc_a.requests == 3 and svc_b.requests == 0
        for _ in range(2):              # affinity 5 -> host index 1
            pool.call("select_build", payload, affinity=5)
        assert svc_b.requests == 2
        svc_a.close()
        svc_b.close()
