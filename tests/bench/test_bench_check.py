"""The check that decides ``correct``: a whole run at a tiny size on the
CPU (the harness's look for a chip skipped) passes on the sound program
and fails with the control in the program's place."""
import time

import numpy as np
import pytest

from bench_tiny_root import make_root
from bench import harness


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    # the persistent cache is process-wide JAX state; tests leave it alone
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: None)


def run(root, cell, **kw):
    return harness.run(root, cell, 2 ** 31 + 99, 1.5, False,
                       need_chip=False, **kw)


@pytest.mark.parametrize("cell", ["gcn-tiny.zipf", "gat-tiny.zipf"])
def test_sound_program_is_correct(root, cell):
    r = run(root, cell)
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] == 60
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {m["name"] for m in
                                 harness.load_cell(root, cell).end_to_end}


@pytest.mark.parametrize("cell", ["gcn-tiny.zipf", "gat-tiny.zipf"])
def test_control_in_the_programs_place_is_not_correct(root, cell):
    """The plain reference computed in bfloat16, the next precision below
    the configuration's float32, stands in for the served answers."""
    c = harness.load_cell(root, cell)
    seen = {}

    def control(targets, served):
        uniq = sorted(set(targets))
        rows = harness.reference_rows(c, seen["graph"], seen["params"], uniq,
                                      harness.CONTROL)["control_bf16"]
        return rows[[uniq.index(t) for t in targets]]

    def keep(dep):
        seen["graph"], seen["params"] = dep.graph, dep.params

    r = run(root, cell, chaos=keep, served_override=control)
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


def test_traced_run_marks_the_whole_window_with_the_profiler_already_on(
        root, monkeypatch):
    """The profiler starts in set-up and stops after the drain, so that
    neither stall falls inside the window; the traced span is the window."""
    import jax
    events = []
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: (
        events.append(("start", time.perf_counter())), start(*a, **k))[1])
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: (
        events.append(("stop", time.perf_counter())), stop())[1])
    opened = []
    window = harness.run_window

    def run_window(*a, **k):
        opened.append(time.perf_counter())
        out = window(*a, **k)
        opened.append(out[-1])
        return out
    monkeypatch.setattr(harness, "run_window", run_window)
    r = harness.run(root, "gcn-tiny.zipf", 17, 1.5, True, need_chip=False)
    assert r["correct"] is True
    assert [e for e, _ in events] == ["start", "stop"]
    (_, t_start), (_, t_stop) = events
    t_call, (t0, end) = opened
    assert t_start < t_call < t0 and t_stop > end
    assert abs(r["device"]["window_s"] - 1.5) < 0.1
    assert set(r["metrics"]) <= {m["name"] for m in
                                 harness.load_cell(root, "gcn-tiny.zipf")
                                 .per_layer}
