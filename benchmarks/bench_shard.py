"""Sharded feature store benchmark: 1/2/4 shards under Zipf traffic.

The regime the sharded store exists for: a feature matrix LARGER than any
single shard's HBM budget. The unsharded resident store must then ship a
per-batch miss block (the paper's t_load re-paid on every cold row); the
sharded store splits the table so the UNION of shard budgets covers the
matrix and every batch stays index-only — per-shard int32 slot lists, a
reorder map, and (ideally) an empty miss block.

Per configuration the benchmark reports p50/p99 closed-loop latency,
host->device bytes per batch, the feature-byte share of it (index_only =
no dense fallback), resident hit rate, and per-shard traffic balance. A
final row re-runs the 4-shard config after ``repin()`` (online PPR-mass
rebalancing) to show the observed-mass residency beating the degree
prior. Appends ``results/BENCH_shard.json`` — a trajectory artifact.

    python benchmarks/bench_shard.py [--smoke] [--requests N] [--zipf A]
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



def make_policies(shard_budget: int, nbr_capacity: int) -> dict:
    """Every config gets the SAME per-shard budget (smaller than the
    feature matrix — that is the point): 1 shard can only hold a slice,
    2/4 shards progressively cover it."""
    shard = dict(features="sharded", placement="range",
                 shard_budget_bytes=shard_budget, nbr_cache="lru",
                 nbr_capacity=nbr_capacity)
    return {
        "resident-1shard": StorePolicy(
            features="resident", hbm_budget_bytes=shard_budget,
            nbr_cache="lru", nbr_capacity=nbr_capacity),
        "sharded-1": StorePolicy(**dict(shard, num_shards=1)),
        "sharded-2": StorePolicy(**dict(shard, num_shards=2)),
        "sharded-4": StorePolicy(**dict(shard, num_shards=4)),
    }


def run_policy(name: str, policy: StorePolicy, g, cfg, params,
               batch_size: int, warm: np.ndarray, meas: np.ndarray,
               repin_between: bool = False) -> dict:
    c = batch_size
    with DecoupledEngine(g, cfg, params=params,
                         config=ServingConfig(batch_size=c,
                                              store=policy)) as eng:
        for i in range(0, len(warm), c):           # compile + cache warmup
            eng.submit_chunk(warm[i:i + c]).result()
        if repin_between:                          # online rebalance from
            eng.repin()                            # the warmup's PPR mass
        s = eng.scheduler.stats
        base = (s.bytes_shipped, s.bytes_dense, s.n_batches,
                list(s.shard_bytes))
        st = eng._fsource
        lk0 = getattr(st, "lookups", 0)
        res0 = getattr(st, "resident_lookups", 0)
        miss0 = getattr(st, "miss_rows_shipped", 0)
        lats = []
        t0 = time.perf_counter()
        for i in range(0, len(meas), c):           # one batch in flight
            tb = time.perf_counter()
            eng.submit_chunk(meas[i:i + c]).result()
            lats.append(time.perf_counter() - tb)
        wall = time.perf_counter() - t0
        shipped = s.bytes_shipped - base[0]
        dense = s.bytes_dense - base[1]
        n_batches = s.n_batches - base[2]
        shard_bytes = [b - b0 for b, b0 in
                       zip(s.shard_bytes, base[3])] if s.shard_bytes \
            else []
        lk = getattr(st, "lookups", 0) - lk0
        res = getattr(st, "resident_lookups", 0) - res0
        miss_rows = getattr(st, "miss_rows_shipped", 0) - miss0
        # feature bytes per batch = miss rows only (slot/reorder maps are
        # the index-only traffic); dense fallback would be C*N*f per batch
        feat_bytes = miss_rows * g.feature_dim * 4
        lat = np.array(lats)
        mean = (sum(shard_bytes) / len(shard_bytes)) if shard_bytes else 0
        return {"policy": name,
                "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
                "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
                "req_per_s": round(len(meas) / wall, 1),
                "bytes_per_batch": int(shipped / max(1, n_batches)),
                "feat_bytes_per_batch": int(feat_bytes
                                            / max(1, n_batches)),
                "index_only": bool(miss_rows == 0),
                "transfer_savings_x": round(dense / shipped, 2)
                if shipped else 0.0,
                "hit_rate": round(res / lk, 4) if lk else 1.0,
                "shard_balance": round(max(shard_bytes) / mean, 3)
                if mean else 1.0,
                "store": eng.store_report()}


def run(requests: int = 4096, batch_size: int = 16, scale: float = 0.05,
        receptive_field: int = 64, zipf_a: float = 1.1,
        nbr_capacity: int = 1024, warm_fraction: float = 0.25,
        budget_fraction: float = 0.3, seed: int = 0):
    import jax

    from repro.gnn.model import init_gnn

    g = get_graph("flickr", scale=scale, seed=seed)
    cfg = GNNConfig(kind="gcn", n_layers=2,
                    receptive_field=receptive_field, f_in=g.feature_dim)
    params = init_gnn(cfg, jax.random.PRNGKey(seed))
    targets = zipf_traffic(g, requests, zipf_a, seed + 1)
    n_warm = int(len(targets) * warm_fraction) // batch_size * batch_size
    warm, meas = targets[:n_warm], targets[n_warm:]
    matrix_bytes = g.num_vertices * g.feature_dim * 4
    # per-shard budget: a FRACTION of the matrix — no single shard can
    # hold it, 4 shards' union can (4 * 0.3 > 1)
    shard_budget = int(matrix_bytes * budget_fraction)
    print(f"graph: V={g.num_vertices} f={g.feature_dim} "
          f"(matrix {matrix_bytes >> 20} MiB) | Zipf({zipf_a}) "
          f"{requests} requests ({n_warm} warmup), C={batch_size} "
          f"N={receptive_field} | per-shard budget "
          f"{shard_budget >> 20} MiB = {budget_fraction:.0%} of matrix")

    rows = []
    policies = make_policies(shard_budget, nbr_capacity)
    for name, policy in policies.items():
        row = run_policy(name, policy, g, cfg, params, batch_size,
                         warm, meas)
        rows.append(row)
        print(f"  [{name}] p50={row['p50_ms']}ms "
              f"bytes/batch={row['bytes_per_batch']} "
              f"feat_bytes/batch={row['feat_bytes_per_batch']} "
              f"index_only={row['index_only']} "
              f"hit={row['hit_rate']} bal={row['shard_balance']}",
              flush=True)
    # online rebalancing: same 4-shard config, repin() after warmup
    row = run_policy("sharded-4+repin", policies["sharded-4"], g, cfg,
                     params, batch_size, warm, meas, repin_between=True)
    rows.append(row)
    print(f"  [sharded-4+repin] p50={row['p50_ms']}ms "
          f"feat_bytes/batch={row['feat_bytes_per_batch']} "
          f"hit={row['hit_rate']} bal={row['shard_balance']}", flush=True)

    print()
    print_table(rows, ["policy", "p50_ms", "p99_ms", "req_per_s",
                       "bytes_per_batch", "feat_bytes_per_batch",
                       "index_only", "hit_rate", "shard_balance"])
    payload = {"rows": rows, "zipf_a": zipf_a, "requests": requests,
               "batch_size": batch_size,
               "receptive_field": receptive_field,
               "num_vertices": g.num_vertices,
               "feature_dim": g.feature_dim,
               "matrix_bytes": matrix_bytes,
               "shard_budget_bytes": shard_budget}
    record_trajectory("shard", payload)
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
    ap.add_argument("--budget-fraction", type=float, default=0.3)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny graph + few requests (CI canary)")
    a = ap.parse_args()
    if a.smoke:
        run_suite(quick=True)
    else:
        run(requests=a.requests, batch_size=a.batch_size, zipf_a=a.zipf,
            budget_fraction=a.budget_fraction)
