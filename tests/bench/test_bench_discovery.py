"""BENCHMARK.json against the harness: every name resolves to its file,
and a new configuration, traffic mix or metric is found by adding files
and entries alone."""
import json
import os
import re

import numpy as np
import pytest

from bench_tiny_root import REPO, make_root
from bench import harness, loadgen

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BM = json.load(_f)
CELLS = [w["name"] for w in BM["workloads"]]


def test_benchmark_file_keeps_to_its_shape():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert 1 <= BM["run_seconds"] <= 51
    for p in BM["paths"]:
        assert os.path.isdir(os.path.join(REPO, p))
    e2e = {m["name"] for m in BM["end_to_end"]}
    assert "setup_s" in e2e
    names = [c["name"] for c in BM["configs"]] + CELLS \
        + [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
    for w in BM["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
    layers = {}
    for m in BM["per_layer"]:
        assert m["moves"] in e2e and UNIT.match(m["unit"])
        assert set(m["workloads"]) <= set(CELLS)
        layers.setdefault(m["layer"], []).append(m["name"])
    assert len(layers) >= 8


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_its_files(cell):
    c = harness.load_cell(REPO, cell)
    assert c.config["name"] == c.workload["config"]
    assert c.mix["rate_per_s"] > 0
    readers = c.metric_readers()          # also checks each declaration
    assert set(readers) == {m["name"] for m in c.per_layer}
    assert hasattr(c.model_module(), "forward")
    loadgen.sampler(c.mix["targets"]["kind"])


def test_new_files_are_found_without_editing_any_other(tmp_path):
    root = make_root(tmp_path, kinds=("gcn",))
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "traffic", "hot.py"), "w") as f:
        f.write("import numpy as np\n\n\n"
                "def sample(rng, degrees, n, *, vertex):\n"
                "    return np.full(n, vertex)\n")
    with open(os.path.join(bench, "traffic", "hot-one.json"), "w") as f:
        json.dump({"targets": {"kind": "hot", "vertex": 3},
                   "rate_per_s": 10, "warmup_requests": 4}, f)
    with open(os.path.join(bench, "metrics", "requests_seen.py"), "w") as f:
        f.write('LAYER = "load generator"\nUNIT = "count"\n'
                'SOURCE = "host_clock"\nMOVES = "latency_p50_ms"\n'
                'BETTER = "higher"\n\n\ndef read(run):\n'
                '    return len(run.lat)\n')
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["workloads"].append({"name": "gcn-tiny.hot", "config": "gcn-tiny",
                            "traffic": "hot-one", "chips": 1, "why": "t"})
    bm["per_layer"].append({"name": "requests_seen", "unit": "count",
                            "better": "higher", "source": "host_clock",
                            "layer": "load generator",
                            "moves": "latency_p50_ms",
                            "workloads": ["gcn-tiny.hot"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    cell = harness.load_cell(root, "gcn-tiny.hot")
    assert [m["name"] for m in cell.per_layer] == ["requests_seen"]
    sched = loadgen.window_schedule(cell.mix, 1, 2.0, np.arange(10),
                                    traffic_dir=os.path.join(bench,
                                                             "traffic"))
    assert len(sched.due) == 20 and set(sched.targets) == {3}
    record = harness.RunRecord(cell=cell, peaks={}, seconds=2.0,
                               lat=np.zeros(7), lag=np.zeros(7), before={},
                               after={}, compiles=0)
    assert harness.layer_metrics(cell, record) == {
        "requests_seen": {"value": 7.0, "unit": "count"}}
    # the old cell is untouched by the new entries
    assert "requests_seen" not in {
        m["name"] for m in harness.load_cell(root, "gcn-tiny.zipf").per_layer}


def test_a_reader_that_disagrees_with_its_entry_is_refused(tmp_path):
    root = make_root(tmp_path, kinds=("gcn",))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["per_layer"][0]["layer"] = "somewhere else"
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    with pytest.raises(ValueError, match="layer"):
        harness.load_cell(root, "gcn-tiny.zipf").metric_readers()
