"""Staged host pipeline benchmark: BatchPlan stages vs the monolithic
host_fn, and the Build-skip win from the subgraph-row cache.

The host side of ``prepare()`` is now three named stages (Select ->
Build -> Pack, core/batchplan.py) that the scheduler pipelines across
consecutive batches, with the Build stage's output cached per target
(``SubgraphRowCache``). This benchmark drives Zipf traffic through four
configurations of the SAME engine:

  monolithic    the one-stage host_fn back-compat spelling (the pre-
                refactor shape: one opaque prepare() on a host pool)
  staged        the per-stage pipelined executor, no caches
  staged+nbr    + neighborhood cache (Select hits skip the PPR push)
  staged+rows   + subgraph-row cache (Build hits skip induced-subgraph
                construction entirely — the ROADMAP's Build-skip win)

Per configuration it reports closed-loop p50/p99, mean host prep time per
batch, and the per-stage wall-time breakdown (the software Fig. 3) with
nbr/build cache hit rates. Appends ``results/BENCH_pipeline.json``.

    python benchmarks/bench_pipeline.py [--smoke] [--requests N] [--zipf A]
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
from repro.core.scheduler import PipelineScheduler
from repro.gnn.model import GNNConfig
from repro.graphs.synthetic import get_graph, zipf_traffic
from repro.store import StorePolicy



def make_policies(nbr_capacity: int) -> dict:
    return {
        "monolithic": StorePolicy(),
        "staged": StorePolicy(),
        "staged+nbr": StorePolicy(nbr_cache="lru",
                                  nbr_capacity=nbr_capacity,
                                  subgraph_rows="off"),
        "staged+rows": StorePolicy(nbr_cache="lru",
                                   nbr_capacity=nbr_capacity,
                                   subgraph_rows="on"),
    }


def run_policy(name: str, policy: StorePolicy, g, cfg, params,
               batch_size: int, warm: np.ndarray, meas: np.ndarray) -> dict:
    c = batch_size
    with DecoupledEngine(g, cfg, params=params,
                         config=ServingConfig(batch_size=c,
                                              store=policy)) as eng:
        if name == "monolithic":
            # the one-stage back-compat spelling: ONE opaque host_fn on a
            # depth-worker pool (the pre-refactor pipeline shape)
            eng.scheduler = PipelineScheduler(eng.prepare, eng.run_device,
                                              depth=3)
        for i in range(0, len(warm), c):           # compile + cache warmup
            eng.submit_chunk(warm[i:i + c]).result()
        s = eng.scheduler.stats
        base_host = s.t_host_total
        base_batches = s.n_batches
        base_stages = dict(s.stage_times)
        base_build = (s.build_hits, s.build_misses)
        base_nbr = (s.cache_hits, s.cache_misses)
        lats = []
        t0 = time.perf_counter()
        for i in range(0, len(meas), c):           # one batch in flight
            tb = time.perf_counter()
            eng.submit_chunk(meas[i:i + c]).result()
            lats.append(time.perf_counter() - tb)
        wall = time.perf_counter() - t0
        n_batches = s.n_batches - base_batches
        host_ms = (s.t_host_total - base_host) / max(1, n_batches) * 1e3
        stages_ms = {k: round((v - base_stages.get(k, 0.0))
                              / max(1, n_batches) * 1e3, 3)
                     for k, v in s.stage_times.items()}
        bh = s.build_hits - base_build[0]
        bm = s.build_misses - base_build[1]
        nh = s.cache_hits - base_nbr[0]
        nm = s.cache_misses - base_nbr[1]
        lat = np.array(lats)
        return {"config": name,
                "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
                "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
                "req_per_s": round(len(meas) / wall, 1),
                "host_ms_per_batch": round(host_ms, 3),
                "stages_ms": stages_ms,
                "select_ms": stages_ms.get("select", ""),
                "build_ms": stages_ms.get("build", ""),
                "pack_ms": stages_ms.get("pack", ""),
                "nbr_hit_rate": round(nh / (nh + nm), 4)
                if nh + nm else 0.0,
                "build_hit_rate": round(bh / (bh + bm), 4)
                if bh + bm else 0.0}


def run(requests: int = 4096, batch_size: int = 16, scale: float = 0.05,
        receptive_field: int = 64, zipf_a: float = 1.1,
        nbr_capacity: int = 1024, warm_fraction: float = 0.25,
        seed: int = 0):
    import jax

    from repro.gnn.model import init_gnn

    g = get_graph("flickr", scale=scale, seed=seed)
    cfg = GNNConfig(kind="gcn", n_layers=2,
                    receptive_field=receptive_field, f_in=g.feature_dim)
    params = init_gnn(cfg, jax.random.PRNGKey(seed))
    targets = zipf_traffic(g, requests, zipf_a, seed + 1)
    n_warm = int(len(targets) * warm_fraction) // batch_size * batch_size
    warm, meas = targets[:n_warm], targets[n_warm:]
    print(f"graph: V={g.num_vertices} f={g.feature_dim} | Zipf({zipf_a}) "
          f"{requests} requests ({n_warm} warmup), C={batch_size} "
          f"N={receptive_field}")

    rows = []
    for name, policy in make_policies(nbr_capacity).items():
        row = run_policy(name, policy, g, cfg, params, batch_size,
                         warm, meas)
        rows.append(row)
        print(f"  [{name}] p50={row['p50_ms']}ms "
              f"host/batch={row['host_ms_per_batch']}ms "
              f"stages={row['stages_ms']} "
              f"nbr_hit={row['nbr_hit_rate']} "
              f"build_hit={row['build_hit_rate']}", flush=True)

    print()
    print_table(rows, ["config", "p50_ms", "p99_ms", "req_per_s",
                       "host_ms_per_batch", "select_ms", "build_ms",
                       "pack_ms", "nbr_hit_rate", "build_hit_rate"])
    by = {r["config"]: r for r in rows}
    if by["staged+rows"]["host_ms_per_batch"] > 0:
        win = by["staged+nbr"]["host_ms_per_batch"] \
            / by["staged+rows"]["host_ms_per_batch"]
        print(f"\nBuild-skip win (staged+nbr -> staged+rows host time): "
              f"{win:.2f}x")
    payload = {"rows": rows, "zipf_a": zipf_a, "requests": requests,
               "batch_size": batch_size,
               "receptive_field": receptive_field,
               "num_vertices": g.num_vertices,
               "feature_dim": g.feature_dim}
    record_trajectory(
        "pipeline", payload,
        regress={"staged_rows_p50_ms": by["staged+rows"]["p50_ms"],
                 "staged_rows_host_ms":
                     by["staged+rows"]["host_ms_per_batch"]})
    return payload


def run_suite(quick: bool = True):
    """benchmarks.run harness entry (quick == CI smoke shape)."""
    if quick:
        return run(requests=640, batch_size=8, scale=0.004,
                   receptive_field=32, nbr_capacity=256,
                   warm_fraction=0.4)
    return run()


if __name__ == "__main__":
    enable_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=4096)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--zipf", type=float, default=1.1)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny graph + few requests (CI canary)")
    a = ap.parse_args()
    if a.smoke:
        run_suite(quick=True)
    else:
        run(requests=a.requests, batch_size=a.batch_size, zipf_a=a.zipf)
