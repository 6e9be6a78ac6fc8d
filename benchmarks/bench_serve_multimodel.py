"""Multi-model streaming serving benchmark (paper §4.4/§4.5 at serving
scale): ONE GNNServer hosting GCN + GraphSAGE + GAT engines over one graph
under a single shared DSEPlan, fed a mixed open-loop request stream.

Reports, per model: request latency p50/p90/p99, batch latency, achieved
host/device overlap fraction of its persistent pipeline — plus aggregate
throughput and the shared plan the models were admitted under.

    python benchmarks/bench_serve_multimodel.py [--smoke] [--requests N]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from benchmarks.common import enable_cache, print_table, record_trajectory
from repro.core.config import ServingConfig
from repro.core.engine import DecoupledEngine
from repro.gnn.model import GNNConfig
from repro.graphs.synthetic import get_graph
from repro.serve.gnn_server import GNNServer

MODEL_KINDS = ("gcn", "sage", "gat")


def run(requests: int = 384, batch_size: int = 16, scale: float = 0.03,
        receptive_field: int = 64, rate_rps: float = 0.0, seed: int = 0):
    g = get_graph("flickr", scale=scale, seed=seed)
    engines = {}
    for kind in MODEL_KINDS:
        cfg = GNNConfig(kind=kind, n_layers=2,
                        receptive_field=receptive_field,
                        f_in=g.feature_dim)
        engines[kind] = DecoupledEngine(
            g, cfg, config=ServingConfig(batch_size=batch_size))

    srv = GNNServer(max_wait_s=0.02)
    for kind, eng in engines.items():
        srv.register(kind, eng)
    print(f"shared plan: BF={srv.plan.block_f} c_core={srv.plan.c_core} "
          f"vmem={srv.plan.vmem_used >> 10}KiB "
          f"models={sorted(srv.models)}")
    srv.start()

    # warm each model's compiled program out of the measurement
    for kind in MODEL_KINDS:
        engines[kind].infer(np.zeros(batch_size, np.int64), overlap=False)

    rng = np.random.default_rng(seed + 1)
    kinds = rng.choice(MODEL_KINDS, size=requests)
    targets = rng.integers(0, g.num_vertices, size=requests)
    gap = 1.0 / rate_rps if rate_rps > 0 else 0.0
    t0 = time.perf_counter()
    reqs = []
    for k, t in zip(kinds, targets):
        reqs.append(srv.submit(int(t), model=str(k)))
        if gap:
            time.sleep(gap)
    srv.drain(reqs, timeout=1200)
    wall = time.perf_counter() - t0
    srv.stop()

    rep = srv.report()
    rows = []
    for kind in MODEL_KINDS:
        m = rep["models"][kind]
        lat = m["latency"]
        rows.append({"model": kind, "n": lat["n"],
                     "p50_ms": round(lat["p50"] * 1e3, 2),
                     "p90_ms": round(lat["p90"] * 1e3, 2),
                     "p99_ms": round(lat["p99"] * 1e3, 2),
                     "batch_ms": round(lat["batch_mean"] * 1e3, 2),
                     "overlap": m["stages"]["overlap"],
                     "sched_batches": m["stages"]["batches"]})
    print_table(rows, ["model", "n", "p50_ms", "p90_ms", "p99_ms",
                       "batch_ms", "overlap", "sched_batches"])
    print(f"\n{requests} requests over {len(MODEL_KINDS)} models in "
          f"{wall:.2f}s ({requests / wall:.0f} req/s aggregate)")
    payload = {"rows": rows, "wall_s": wall,
               "req_per_s": requests / wall, "plan": rep["plan"],
               "batch_size": batch_size, "requests": requests}
    record_trajectory("serve_multimodel", payload)
    for eng in engines.values():
        eng.close()
    return payload


def run_suite(quick: bool = True):
    """benchmarks.run harness entry (quick == CI smoke shape)."""
    if quick:
        return run(requests=48, batch_size=8, scale=0.01,
                   receptive_field=32)
    return run()


if __name__ == "__main__":
    enable_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=384)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--rate-rps", type=float, default=0.0,
                    help="open-loop arrival rate; 0 = as fast as possible")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny graph + few requests (CI canary)")
    a = ap.parse_args()
    if a.smoke:
        run_suite(quick=True)
    else:
        run(requests=a.requests, batch_size=a.batch_size,
            rate_rps=a.rate_rps)
