"""Store-subsystem benchmark: Zipf-skewed traffic vs. cache policy.

Production mini-batch inference traffic is popularity-skewed: a small hot
set of targets absorbs most requests, and PPR neighborhoods are hub-heavy,
so the dense baseline re-runs local push and re-ships the same feature
rows thousands of times (paper Eq. 2: t_pre + t_load paid in full every
batch). This benchmark drives the same Zipf(a) request stream through one
engine per store policy and reports what the two-level store buys:

  cold      dense shipping, no neighborhood cache   (the seed baseline)
  lru       dense shipping + LRU neighborhood cache
  pinned    dense shipping + LRU + pinned top-degree hot set
  packed    cross-target dedup shipping + LRU cache
  resident  device feature store (full-resident)    + LRU cache

Popularity rank follows vertex degree (hubs are hot — the realistic and
adversarially *cacheable* regime the store targets). Latency is measured
closed-loop, one batch in flight, so p50/p99 reflect per-batch work and
not queueing. Emits ``results/BENCH_store.json`` — a trajectory artifact
appended per run (p50/p99, bytes shipped, hit rates per policy).

    python benchmarks/bench_store.py [--smoke] [--requests N] [--zipf A]
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
from repro.graphs.synthetic import get_graph, zipf_traffic
from repro.store import StorePolicy



def make_policies(nbr_capacity: int) -> dict:
    return {
        "cold": StorePolicy(),
        "lru": StorePolicy(nbr_cache="lru", nbr_capacity=nbr_capacity),
        "pinned": StorePolicy(nbr_cache="pinned",
                              nbr_capacity=nbr_capacity,
                              pinned_count=max(1, nbr_capacity // 4)),
        "packed": StorePolicy(features="packed", nbr_cache="lru",
                              nbr_capacity=nbr_capacity),
        "resident": StorePolicy(features="resident", nbr_cache="lru",
                                nbr_capacity=nbr_capacity),
    }




def run_policy(name: str, policy: StorePolicy, g, cfg, params,
               batch_size: int, warm: np.ndarray, meas: np.ndarray) -> dict:
    c = batch_size
    with DecoupledEngine(g, cfg, params=params,
                         config=ServingConfig(batch_size=c,
                                              store=policy)) as eng:
        for i in range(0, len(warm), c):           # compile + cache warmup
            eng.submit_chunk(warm[i:i + c]).result()
        s = eng.scheduler.stats
        base = (s.bytes_shipped, s.bytes_dense, s.cache_hits,
                s.cache_misses, s.n_batches)
        lats = []
        t0 = time.perf_counter()
        for i in range(0, len(meas), c):           # one batch in flight
            tb = time.perf_counter()
            eng.submit_chunk(meas[i:i + c]).result()
            lats.append(time.perf_counter() - tb)
        wall = time.perf_counter() - t0
        shipped = s.bytes_shipped - base[0]
        dense = s.bytes_dense - base[1]
        hits = s.cache_hits - base[2]
        misses = s.cache_misses - base[3]
        n_batches = s.n_batches - base[4]
        lat = np.array(lats)
        return {"policy": name,
                "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
                "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
                "req_per_s": round(len(meas) / wall, 1),
                "bytes_per_batch": int(shipped / max(1, n_batches)),
                "transfer_savings_x": round(dense / shipped, 2)
                if shipped else 0.0,
                "nbr_hit_rate": round(hits / (hits + misses), 4)
                if hits + misses else 0.0,
                "store": eng.store_report()}


def run(requests: int = 4096, batch_size: int = 16, scale: float = 0.05,
        receptive_field: int = 64, zipf_a: float = 1.1,
        nbr_capacity: int = 1024, warm_fraction: float = 0.25,
        seed: int = 0):
    import jax

    from repro.gnn.model import init_gnn

    g = get_graph("flickr", scale=scale, seed=seed)
    cfg = GNNConfig(kind="gcn", n_layers=2,
                    receptive_field=receptive_field, f_in=g.feature_dim)
    # one parameter set shared across policies (same model, so latency
    # differences are purely the store's doing)
    params = init_gnn(cfg, jax.random.PRNGKey(seed))
    # traffic model lives with the synthetic datasets (zipf_traffic) so
    # the benchmark, examples, and cache tests sample one distribution
    targets = zipf_traffic(g, requests, zipf_a, seed + 1)
    n_warm = int(len(targets) * warm_fraction) // batch_size * batch_size
    warm, meas = targets[:n_warm], targets[n_warm:]
    print(f"graph: V={g.num_vertices} f={g.feature_dim} | Zipf({zipf_a}) "
          f"{requests} requests ({n_warm} warmup), C={batch_size} "
          f"N={receptive_field}, nbr_capacity={nbr_capacity}")

    rows = []
    for name, policy in make_policies(nbr_capacity).items():
        row = run_policy(name, policy, g, cfg, params, batch_size,
                         warm, meas)
        rows.append(row)
        print(f"  [{name}] p50={row['p50_ms']}ms p99={row['p99_ms']}ms "
              f"bytes/batch={row['bytes_per_batch']} "
              f"savings={row['transfer_savings_x']}x "
              f"hit_rate={row['nbr_hit_rate']}", flush=True)

    print()
    print_table(rows, ["policy", "p50_ms", "p99_ms", "req_per_s",
                       "bytes_per_batch", "transfer_savings_x",
                       "nbr_hit_rate"])
    payload = {"rows": rows, "zipf_a": zipf_a, "requests": requests,
               "batch_size": batch_size,
               "receptive_field": receptive_field,
               "nbr_capacity": nbr_capacity,
               "num_vertices": g.num_vertices,
               "feature_dim": g.feature_dim}
    best = min(r["p50_ms"] for r in rows)
    record_trajectory("store", payload,
                      regress={"best_policy_p50_ms": best})
    return payload


def run_suite(quick: bool = True):
    """benchmarks.run harness entry (quick == CI smoke shape).

    The quick graph is small enough (V~180) that 640 Zipf(1.1) requests
    reach steady state — hit rate asymptotes only once the stream has
    covered the head of the popularity distribution."""
    if quick:
        return run(requests=640, batch_size=8, scale=0.002,
                   receptive_field=32, nbr_capacity=256,
                   warm_fraction=0.4)
    return run()


if __name__ == "__main__":
    enable_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=4096)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--zipf", type=float, default=1.1)
    ap.add_argument("--nbr-capacity", type=int, default=1024)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny graph + few requests (CI canary)")
    a = ap.parse_args()
    if a.smoke:
        run_suite(quick=True)
    else:
        run(requests=a.requests, batch_size=a.batch_size, zipf_a=a.zipf,
            nbr_capacity=a.nbr_capacity)
