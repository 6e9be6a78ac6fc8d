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


def tiny_config(kind: str) -> dict:
    with open(os.path.join(REPO, "bench", "configs",
                           f"{kind}-flickr.json")) as f:
        c = json.load(f)
    c["name"] = f"{kind}-tiny"
    c["model"].update(receptive_field=16, f_in=40, f_hidden=32, n_layers=2)
    c["graph"].update(num_vertices=1500, feature_dim=40, seed=3)
    c["serving"].update(batch_size=8, impl="xla", num_threads=2)
    c["serving"]["store"]["nbr_capacity"] = 64
    c["check"]["sample"] = 24
    return c


def make_root(tmp: str, kinds=("gcn", "gat")) -> str:
    """``tmp`` laid out as a checkout: BENCHMARK.json naming one tiny
    Zipf cell per model kind, and a copy of bench/ holding their files."""
    root = os.path.join(str(tmp), "checkout")
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bm = json.load(f)
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
    cells = [w["name"] for w in bm["workloads"]]
    for m in bm["per_layer"]:
        m["workloads"] = [c for c in cells
                          if m["name"] != "gat_attention_roofline"
                          or c.startswith("gat")]
    with open(os.path.join(root, "bench", "traffic", "zipf-tiny.json"),
              "w") as f:
        json.dump({"targets": {"kind": "zipf", "a": 1.1}, "rate_per_s": 40,
                   "warmup_requests": 48}, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return root
