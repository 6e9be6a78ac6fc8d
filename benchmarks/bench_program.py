"""AckProgram per-op mode dispatch benchmark.

For every model kind, the same engine/traffic is run four ways:

  dense     every mux'd op forced to the systolic datapath
  sg        every mux'd op forced to the scatter-gather datapath
  auto      static per-op dispatch — each Aggregate / AttentionSoftmax
            picks its own mode ONCE from its kernel's FLOP model
            (Transform stays systolic)
  adaptive  per-BATCH dispatch — every batch re-decides from measured
            densities + the calibration table's p50s (warmup passes
            sample both modes, then the table drives; core.dispatch)

Two regimes are driven: the paper's hub-dense PPR subgraphs (auto should
track the dense forcing) and an ultra-sparse graph (auto should flip the
aggregation ops to sg while the wide transforms stay dense — the
heterogeneous program the IR exists for; its per-op decision list is
printed). The acceptance bar for the adaptive lane is printed per cell:
its p50 must track the best forced mode within 5%. Emits
``results/BENCH_program.json`` — a trajectory artifact appended per run;
per-cell adaptive p50s and adaptive/best-forced ratios feed the regress
gate.

    python benchmarks/bench_program.py [--smoke] [--requests N]
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
from repro.graphs.csr import from_edge_list
from repro.graphs.synthetic import get_graph


KINDS = ("gcn", "sage", "gin", "gat")


def sparse_graph(v=2048, edges=256, f=64, seed=0):
    """Mean degree << 1: the regime where sg aggregation wins (N > 2E)."""
    rng = np.random.default_rng(seed)
    src = rng.choice(v, edges, replace=False)
    dst = (src + 1 + rng.integers(0, v - 1, edges)) % v
    feats = rng.standard_normal((v, f)).astype(np.float32)
    return from_edge_list(src, dst, v, feats, name="ultra-sparse")


WARMUP_PASSES = 2      # adaptive lane: forced samples per mode per bucket
REPS = 5               # timed passes over the target list per lane
MODES = ("dense", "sg", "auto", "adaptive")


def run_kind(g, cfg, params, targets, batch_size):
    """Time all four lanes INTERLEAVED chunk-by-chunk in one window.

    These latencies are host-pipeline dominated (~10ms/chunk) on a
    shared CPU whose load drifts over minutes; running the lanes
    sequentially bakes that drift into the cross-lane ratios. Rotating
    every chunk through all four engines back-to-back makes each lane
    sample the same noise distribution, so the p50 ratios isolate the
    dispatch overhead the acceptance bar is about."""
    import jax
    from repro.core.dispatch import DispatchConfig
    lanes = {}
    for mode in MODES:
        if mode == "adaptive":
            sconf = ServingConfig(
                batch_size=batch_size, mode="auto",
                dispatch=DispatchConfig(warmup_passes=WARMUP_PASSES))
        else:
            sconf = ServingConfig(batch_size=batch_size, mode=mode)
        lanes[mode] = DecoupledEngine(g, cfg, params=params, config=sconf)
    lats = {m: [] for m in MODES}
    try:
        for mode, eng in lanes.items():
            # warm the compile out of the measurement; the adaptive lane
            # also burns through the exploration schedule (2*passes
            # forced samples per mode) plus one chunk to jit the
            # exploited variant, so the timed window measures
            # steady-state measured-cost dispatch
            n_warm = 2 * WARMUP_PASSES + 2 if mode == "adaptive" else 1
            for k in range(n_warm):
                lo = (k * batch_size) % max(len(targets) - batch_size, 1)
                w = eng.submit_chunk(targets[lo:lo + batch_size]).result()
            jax.block_until_ready(w)
        for _ in range(REPS):
            for i in range(0, len(targets), batch_size):
                chunk = targets[i:i + batch_size]
                for mode, eng in lanes.items():
                    t0 = time.perf_counter()
                    eng.submit_chunk(chunk).result()
                    lats[mode].append(time.perf_counter() - t0)
        out = {}
        for mode, eng in lanes.items():
            lat = np.array(lats[mode])
            dec = eng.decision
            r = {"mode": mode,
                 "resolved": dec.mode,
                 "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
                 "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
                 "ops": [{"site": d.site, "op": d.op, "mode": d.mode}
                         for d in dec],
                 "n_dense": dec.n_dense, "n_sg": dec.n_sg}
            if mode == "adaptive":
                r["dispatch"] = eng.dispatch_report()
            out[mode] = r
    finally:
        for eng in lanes.values():
            eng.close()
    return out


def bench_regime(name, g, kinds, requests, batch_size, receptive_field,
                 f_hidden, seed=0):
    import jax

    from repro.gnn.model import init_gnn
    print(f"\n-- regime: {name} (V={g.num_vertices}, "
          f"E={g.num_edges}, N={receptive_field}) --")
    rng = np.random.default_rng(seed)
    pool = np.unique(np.concatenate(
        [np.where(g.degrees > 0)[0], np.arange(min(64, g.num_vertices))]))
    targets = rng.choice(pool, size=requests)
    rows, details = [], {}
    for kind in kinds:
        cfg = GNNConfig(kind=kind, n_layers=2,
                        receptive_field=receptive_field,
                        f_in=g.feature_dim, f_hidden=f_hidden)
        params = init_gnn(cfg, jax.random.PRNGKey(seed))
        row = {"kind": kind}
        res = run_kind(g, cfg, params, targets, batch_size)
        for mode, r in res.items():
            row[f"{mode}_p50_ms"] = r["p50_ms"]
            if mode == "auto":
                row["auto_program"] = f"{r['n_dense']}d+{r['n_sg']}sg"
                details[kind] = r["ops"]
            if mode == "adaptive":
                row["dispatch_sources"] = r["dispatch"]["sources"]
        best = min(row["dense_p50_ms"], row["sg_p50_ms"])
        row["adaptive_ratio"] = round(
            row["adaptive_p50_ms"] / best, 4) if best else 1.0
        rows.append(row)
        flag = "" if row["adaptive_ratio"] <= 1.05 else \
            "  ** >5% over best forced mode **"
        print(f"  [{kind}] dense={row['dense_p50_ms']}ms "
              f"sg={row['sg_p50_ms']}ms auto={row['auto_p50_ms']}ms "
              f"adaptive={row['adaptive_p50_ms']}ms "
              f"(ratio={row['adaptive_ratio']}) "
              f"auto-program={row['auto_program']}{flag}", flush=True)
    print()
    print_table(rows, ["kind", "dense_p50_ms", "sg_p50_ms", "auto_p50_ms",
                       "adaptive_p50_ms", "adaptive_ratio",
                       "auto_program"])
    return rows, details


def run(requests: int = 256, batch_size: int = 8, scale: float = 0.02,
        receptive_field: int = 64, seed: int = 0,
        kinds=KINDS):
    g_dense = get_graph("flickr", scale=scale, seed=seed)
    dense_rows, dense_ops = bench_regime(
        "ppr-dense (paper regime)", g_dense, kinds, requests, batch_size,
        receptive_field, f_hidden=256, seed=seed)

    g_sparse = sparse_graph(seed=seed)
    sparse_rows, sparse_ops = bench_regime(
        "ultra-sparse (mixed per-op regime)", g_sparse, kinds, requests,
        batch_size, receptive_field=32, f_hidden=256, seed=seed)

    mixed = {k: ops for k, ops in sparse_ops.items()
             if {o["mode"] for o in ops} == {"dense", "sg"}}
    print("\nper-op decisions (ultra-sparse, auto):")
    for kind, ops_list in sparse_ops.items():
        print(f"  {kind}: " + ", ".join(
            f"{o['site']} {o['op']}={o['mode']}" for o in ops_list))
    if mixed:
        print(f"\nheterogeneous auto programs (sg aggregation + dense "
              f"transform in ONE compiled program): {sorted(mixed)}")

    # regress gate scalars: per-cell adaptive p50s + adaptive/best-forced
    # ratios, plus the worst ratio across every (kind x regime) cell —
    # the acceptance bar (<= 1.05 everywhere) as a single scalar
    regress, worst = {}, 0.0
    for regime, rows in (("dense", dense_rows), ("sparse", sparse_rows)):
        for row in rows:
            cell = f"{regime}_{row['kind']}"
            regress[f"adaptive_p50_ms_{cell}"] = row["adaptive_p50_ms"]
            regress[f"adaptive_ratio_{cell}"] = row["adaptive_ratio"]
            worst = max(worst, row["adaptive_ratio"])
    regress["adaptive_worst_ratio"] = worst
    if worst > 1.05:
        print(f"\nWARNING: adaptive p50 {worst:.3f}x best forced mode in "
              f"the worst cell (acceptance bar: <= 1.05x)")
    else:
        print(f"\nadaptive lane within 5% of best forced mode in every "
              f"cell (worst ratio {worst:.3f}x)")

    payload = {"requests": requests, "batch_size": batch_size,
               "receptive_field": receptive_field,
               "dense_regime": dense_rows, "sparse_regime": sparse_rows,
               "sparse_auto_ops": sparse_ops,
               "mixed_program_kinds": sorted(mixed),
               "adaptive_worst_ratio": worst}
    record_trajectory("program", payload, regress=regress)
    return payload


def run_suite(quick: bool = True):
    """benchmarks.run harness entry (quick == CI smoke shape)."""
    if quick:
        return run(requests=64, batch_size=8, scale=0.005,
                   receptive_field=32)
    return run()


if __name__ == "__main__":
    enable_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny graph + few requests (CI canary)")
    a = ap.parse_args()
    if a.smoke:
        run_suite(quick=True)
    else:
        run(requests=a.requests, batch_size=a.batch_size)
