"""BENCHMARK.json against the harness: every name resolves to its file,
and a new configuration, traffic mix or metric is found by adding files
and entries alone."""
import dataclasses
import json
import os
import re
import shutil
import typing

import numpy as np
import pytest

from bench_tiny_root import REPO, make_root, tiny_config
from bench import harness, loadgen, tracing

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
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    # at most half of the cells, rounded down, take four chips; one may
    assert sum(w["chips"] == 4 for w in BM["workloads"]) <= max(
        1, len(CELLS) // 2)
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


def test_new_files_are_found_without_editing_any_other(tmp_path,
                                                       monkeypatch):
    root = make_root(tmp_path, kinds=("gcn",))
    bench = os.path.join(root, "bench")
    # a model kind of its own: its config and its model module, with the
    # reference, the model's work and the fused kernel's operands
    shutil.copy(os.path.join(os.path.dirname(__file__), "sage_model.py"),
                os.path.join(bench, "models", "sage.py"))
    sage = tiny_config("gcn")
    sage["name"] = "sage-tiny"
    sage["model"]["kind"] = "sage"
    with open(os.path.join(bench, "configs", "sage-tiny.json"), "w") as f:
        json.dump(sage, f)
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
    bm["configs"].append({"name": "sage-tiny", "source": "test",
                          "file": "bench/configs/sage-tiny.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "sage-tiny.zipf", "config": "sage-tiny",
                            "traffic": "zipf-tiny", "chips": 1, "why": "t"})
    for m in bm["per_layer"]:                 # the metrics GCN's cell has
        if "gcn-tiny.zipf" in m.get("workloads", ()):
            m["workloads"].append("sage-tiny.zipf")
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
    # the new kind serves, checks and is counted by its own module
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: None)
    tracers = []

    class Tracer(tracing.WindowTracer):
        def __init__(self, dep, cell):
            super().__init__(dep, cell)
            tracers.append(self)
    monkeypatch.setattr(tracing, "WindowTracer", Tracer)
    r = harness.run(root, "sage-tiny.zipf", 2 ** 31 + 7, 1.5, True,
                    need_chip=False)
    assert r["correct"] is True and r["failed"] == 0
    (tracer,) = tracers
    module = tracer.cell.model_module()
    assert tracer.cell.model["kind"] == "sage"
    assert module.__file__ == os.path.join(bench, "models", "sage.py")
    work = [w for _, w in tracer._calls]
    counted = list(module.CALLS)
    assert work and all(w > 0 for w in work)
    assert len(counted) >= r["attempted"]
    assert sum(work) == pytest.approx(sum(
        module.model_flops(sage["model"], k, e) for k, e in counted))


def test_a_reader_that_disagrees_with_its_entry_is_refused(tmp_path):
    root = make_root(tmp_path, kinds=("gcn",))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["per_layer"][0]["layer"] = "somewhere else"
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    with pytest.raises(ValueError, match="layer"):
        harness.load_cell(root, "gcn-tiny.zipf").metric_readers()


def test_tiny_cells_take_the_metrics_of_their_kind(tmp_path):
    """A tiny cell carries the per-layer metrics that BENCHMARK.json lists
    for the cells of its model kind. For GCN and GAT that is the list the
    tiny cells carried before metrics were chosen by kind: every metric,
    ``gat_attention_roofline`` for GAT alone."""
    root = make_root(tmp_path)
    for kind in ("gcn", "gat"):
        of_kind = [c for c in CELLS
                   if harness.load_cell(REPO, c).model["kind"] == kind]
        want = [m["name"] for m in BM["per_layer"]
                if any(harness.applies(m, c) for c in of_kind)]
        got = [m["name"] for m in
               harness.load_cell(root, f"{kind}-tiny.zipf").per_layer]
        assert got == want, kind
        assert ("gat_attention_roofline" in got) == (kind == "gat")


# The cells whose configs were built from nine ``model`` and five
# ``serving`` keys named one by one, before every key reached its field.
PINNED = ("gcn-flickr.zipf", "gat-flickr.zipf")


def _field_type(hint):
    """The type a config value of a field hinted ``hint`` takes, or
    ``object`` where any will do."""
    if typing.get_origin(hint) is typing.Union:
        args = [a for a in typing.get_args(hint) if a is not type(None)]
        hint = args[0] if len(args) == 1 else object
    if typing.get_origin(hint) is tuple:
        return tuple
    return hint if isinstance(hint, type) else object


def _assert_built_from(obj, values: dict, where: str):
    """Each key of ``values`` reached the field of that name of ``obj``,
    with the field's type; each field ``values`` leaves out kept its
    default."""
    hints = typing.get_type_hints(type(obj))
    fields = dataclasses.fields(obj)
    assert set(values) <= {f.name for f in fields}, where
    for f in fields:
        got, at = getattr(obj, f.name), f"{where}.{f.name}"
        if f.name not in values:
            default = (f.default if f.default is not dataclasses.MISSING
                       else f.default_factory())
            assert got == default, at
        elif dataclasses.is_dataclass(got):
            _assert_built_from(got, values[f.name], at)
        else:
            want = values[f.name]
            assert got == (tuple(want) if isinstance(want, list) else want), at
            kind = _field_type(hints[f.name])
            exact = kind in (int, float, str, bool, tuple)
            assert got is None or (type(got) is kind if exact
                                   else isinstance(got, kind)), at


@pytest.mark.parametrize("cell", CELLS)
def test_program_configs_are_built_as_before(cell):
    """Every key of ``model`` and ``serving`` reaches the program: each
    field of ``GNNConfig`` and ``ServingConfig`` (and its ``store``) is
    the configured value in the field's type, or its default where the
    config does not set it. The accepted cells' configs also come out
    field by field as the nine and five keys named one by one built
    them."""
    from repro.core.config import ServingConfig
    from repro.gnn.model import GNNConfig
    from repro.store import StorePolicy
    c = harness.load_cell(REPO, cell)
    m, s = c.model, c.config["serving"]
    got_g, got_s = harness.program_configs(c)
    _assert_built_from(got_g, m, "model")
    _assert_built_from(got_s, s, "serving")
    if cell not in PINNED:
        return
    want_g = GNNConfig(kind=m["kind"], n_layers=int(m["n_layers"]),
                       receptive_field=int(m["receptive_field"]),
                       f_in=int(m["f_in"]), f_hidden=int(m["f_hidden"]),
                       n_heads=int(m["n_heads"]), readout=m["readout"],
                       ppr_alpha=float(m["ppr_alpha"]),
                       ppr_eps=float(m["ppr_eps"]))
    want_s = ServingConfig(batch_size=int(s["batch_size"]), impl=s["impl"],
                           mode=s["mode"], num_threads=int(s["num_threads"]),
                           max_wait_s=float(s["max_wait_s"]),
                           store=StorePolicy(**s["store"]))
    for got, want in ((got_g, want_g), (got_s, want_s),
                      (got_s.store, want_s.store)):
        for f in dataclasses.fields(want):
            a, b = getattr(got, f.name), getattr(want, f.name)
            assert a == b and type(a) is type(b), f.name


@pytest.mark.parametrize("group", ["model", "serving", "serving.store"])
def test_a_config_key_the_program_lacks_is_refused_at_load(tmp_path, group):
    from repro.core.config import ServingConfig
    from repro.gnn.model import GNNConfig
    from repro.store import StorePolicy
    key = "no_such_key_17"
    for cls in (GNNConfig, ServingConfig, StorePolicy):
        assert key not in {f.name for f in dataclasses.fields(cls)}
    root = make_root(tmp_path, kinds=("gcn",))
    path = os.path.join(root, "bench", "configs", "gcn-tiny.json")
    with open(path) as f:
        cfg = json.load(f)
    target = cfg
    for part in group.split("."):
        target = target[part]
    target[key] = 1
    with open(path, "w") as f:
        json.dump(cfg, f)
    with pytest.raises(ValueError, match=rf"{group}\.{key}: "):
        harness.load_cell(root, "gcn-tiny.zipf")


def test_config_values_take_their_field_types():
    from repro.core.config import ServingConfig
    s = harness.from_config(ServingConfig, {
        "batch_size": 8.0, "e_pad": None, "transport": "socket",
        "endpoints": ["a:1", "b:2"],
        "store": {"nbr_cache": "pinned", "nbr_capacity": "64",
                  "pinned_targets": [3, 4]}}, "serving")
    assert s.batch_size == 8 and type(s.batch_size) is int
    assert s.e_pad is None and s.endpoints == ("a:1", "b:2")
    assert s.store.nbr_capacity == 64 and s.store.pinned_targets == (3, 4)
