"""The scheduler's hand-off ledger and the stations' profiler annotations:
each ticket's intervals close exactly to its time from submit to
completion, the waits land where the queueing is, and a profile taken on
the CPU holds the ``repro.*`` annotations."""
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.config import ServingConfig
from repro.core.engine import DecoupledEngine
from repro.core.report_schema import SCHEMA
from repro.core.scheduler import PipelineScheduler
from repro.gnn.model import GNNConfig
from repro.graphs.synthetic import get_graph
from repro.serve.gnn_server import GNNServer

C = 4
LEDGER = {"queue.admit", "queue.select", "queue.build", "queue.pack",
          "queue.dispatch", "device.launch", "queue.drain", "device.ready",
          "device.reply"}


@pytest.fixture(scope="module")
def graph():
    return get_graph("flickr", scale=0.02, seed=1)


def _engine(graph, **kw):
    cfg = GNNConfig(kind="gcn", n_layers=2, receptive_field=16,
                    f_in=graph.feature_dim)
    return DecoupledEngine(graph, cfg, config=ServingConfig(
        batch_size=C, num_threads=1, **kw))


class _Sleep:
    """A stage that holds its station for ``s`` seconds."""

    def __init__(self, name, s):
        self.name, self.s, self.workers = name, s, 1

    def run(self, v):
        time.sleep(self.s)
        return v

    def close(self):
        pass


def test_ledger_and_service_times_close_to_the_submit_chunk_call(graph):
    with _engine(graph) as eng:
        eng.infer(np.arange(C))                 # compile out of the way
        tickets = [eng.submit_chunk(np.arange(i, i + C))
                   for i in range(0, 6 * C, C)]
        eng.scheduler.flush(timeout=120)
        for t in tickets:
            assert set(t.ledger) == LEDGER
            assert set(t.stage_times) == {"select", "build", "pack"}
            total = sum(t.ledger.values()) + sum(t.stage_times.values())
            assert total == pytest.approx(t.t_done - t.t_call, abs=1e-6)
            assert all(v >= 0 for v in t.ledger.values())
            # the batch's device time is its own launch and ready wait
            assert t.t_device == pytest.approx(
                t.ledger["device.launch"] + t.ledger["device.ready"],
                abs=1e-9)
        st = eng.scheduler.stats
        assert set(st.service_times) == {"select", "build", "pack"}
        assert LEDGER <= set(st.wait_times)
        # folded at completion: the stats hold every ticket's ledger
        # (the warm-up infer() batch included)
        for key in LEDGER:
            assert st.wait_times[key] >= sum(t.ledger[key]
                                             for t in tickets)


def test_queue_waits_grow_behind_a_busy_stage():
    s = PipelineScheduler([_Sleep("slow", 0.05)], lambda v: jnp.asarray(v),
                          depth=3)
    s.start()
    tickets = [s.submit(i) for i in range(3)]
    s.flush(timeout=30)
    waits = [t.ledger["queue.slow"] for t in tickets]
    assert waits[0] < 0.02
    assert 0.04 < waits[1] < 0.09
    assert 0.09 < waits[2] < 0.15
    assert s.stats.wait_times["queue.slow"] == pytest.approx(sum(waits))
    assert s.stats.service_times["slow"] >= 0.15
    s.close()


def test_in_flight_bound_shows_as_admission_wait():
    s = PipelineScheduler([_Sleep("a", 0.03)], lambda v: jnp.asarray(v),
                          depth=1, max_inflight=1)
    first = s.submit(0)
    second = s.submit(1)            # blocks until the first completes
    s.flush(timeout=30)
    assert first.ledger["queue.admit"] < 0.01
    assert second.ledger["queue.admit"] > 0.02
    assert s.stats.wait_times["queue.admit"] > 0.02
    s.close()


def test_one_stage_host_fn_keeps_a_ledger():
    s = PipelineScheduler(lambda v: v, lambda v: jnp.asarray(v), depth=2)
    t = s.submit(3)
    s.flush(timeout=30)
    assert {"queue.host", "queue.dispatch", "device.launch",
            "device.ready"} <= set(t.ledger)
    assert set(t.stage_times) == {"host"}
    assert sum(t.ledger.values()) + t.stage_times["host"] == \
        pytest.approx(t.t_done - t.t_call, abs=1e-6)
    s.close()


def test_failed_batch_folds_no_waits():
    class _Boom(_Sleep):
        def run(self, v):
            raise ValueError("boom")

    s = PipelineScheduler([_Boom("boom", 0)], lambda v: jnp.asarray(v))
    t = s.submit(0)
    s.flush(timeout=30)
    with pytest.raises(ValueError):
        t.result()
    assert s.stats.wait_times == {}
    assert set(s.stats.service_times) == {"boom"}
    s.close()


def test_lone_request_waits_at_least_max_wait_in_the_lane(graph):
    eng = _engine(graph)
    eng.infer(np.arange(C))
    srv = GNNServer(eng, max_wait_s=0.03)
    srv.start()
    srv.drain([srv.submit(5)], timeout=120)
    srv.stop()
    waits = eng.scheduler.stats.wait_times
    assert waits["queue.lane"] >= 0.03
    stages = srv.report()["models"]["default"]["stages"]
    assert set(stages["times"]) == {"select", "build", "pack"}
    assert "queue.lane" in stages["waits"] and "waits" in SCHEMA["stages"]
    eng.close()


def test_a_cpu_profile_holds_the_station_annotations(graph, tmp_path):
    from jax.profiler import ProfileData
    eng = _engine(graph)
    eng.infer(np.arange(C))
    srv = GNNServer(eng, max_wait_s=0.005)
    srv.start()
    jax.profiler.start_trace(str(tmp_path))
    try:
        srv.drain([srv.submit(i) for i in range(2 * C)], timeout=120)
    finally:
        jax.profiler.stop_trace()
        srv.stop()
        eng.close()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    seen.setdefault(e.name, dict(e.stats))
    for station in ("select", "build", "pack", "device", "drain", "reply"):
        stats = seen[f"repro.{station}"]
        assert {"seq", "queued_us"} <= set(stats)
    assert {"repro.lane.collect", "repro.lane.submit"} <= set(seen)

