"""A checkout-shaped directory with the benchmark and tiny cells, for
tests that drive the harness on the CPU without a timed chip window."""
from __future__ import annotations

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (REPO, os.path.join(REPO, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _load(rel: str) -> dict:
    with open(os.path.join(REPO, rel)) as f:
        return json.load(f)


def _kind(entry: dict) -> str:
    """Model kind of a ``configs`` entry of BENCHMARK.json."""
    return _load(entry["file"])["model"]["kind"]


def tiny_config(kind: str) -> dict:
    """The first configuration of ``kind`` in BENCHMARK.json, at a tiny
    size."""
    entry = next(c for c in _load("BENCHMARK.json")["configs"]
                 if _kind(c) == kind)
    c = _load(entry["file"])
    c["name"] = f"{kind}-tiny"
    c["model"].update(receptive_field=16, f_in=40, f_hidden=32, n_layers=2)
    c["graph"].update(num_vertices=1500, feature_dim=40, seed=3)
    c["serving"].update(batch_size=8, impl="xla", num_threads=2)
    c["serving"]["store"]["nbr_capacity"] = 64
    c["check"]["sample"] = 24
    return c


def make_root(tmp: str, kinds=("gcn", "gat")) -> str:
    """``tmp`` laid out as a checkout: BENCHMARK.json naming one tiny
    Zipf cell per model kind, and a copy of bench/ holding their files.
    A per-layer metric goes to the tiny cell of each kind that some cell
    in its ``workloads`` has; one with no ``workloads`` to every cell."""
    root = os.path.join(str(tmp), "checkout")
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bm = _load("BENCHMARK.json")
    kind_of = {c["name"]: _kind(c) for c in bm["configs"]}
    cell_kind = {w["name"]: kind_of[w["config"]] for w in bm["workloads"]}
    bm["configs"], bm["workloads"] = [], []
    for kind in kinds:
        name = f"{kind}-tiny"
        path = f"bench/configs/{name}.json"
        with open(os.path.join(root, path), "w") as f:
            json.dump(tiny_config(kind), f)
        bm["configs"].append({"name": name, "source": "test", "file": path,
                              "reduced": [], "why": "test"})
        bm["workloads"].append({"name": f"{name}.zipf", "config": name,
                                "traffic": "zipf-tiny", "chips": 1,
                                "why": "test"})
    for m in bm["per_layer"]:
        if "workloads" in m:
            has = {cell_kind[c] for c in m["workloads"]}
            m["workloads"] = [f"{k}-tiny.zipf" for k in kinds if k in has]
    with open(os.path.join(root, "bench", "traffic", "zipf-tiny.json"),
              "w") as f:
        json.dump({"targets": {"kind": "zipf", "a": 1.1}, "rate_per_s": 40,
                   "warmup_requests": 48}, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return root
