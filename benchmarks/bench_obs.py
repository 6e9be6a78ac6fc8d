"""Observability overhead benchmark: tracing AND metrics must be
near-free.

The observability contract is (a) bitwise-identical serving outputs
instrumented or not, and (b) <5% p50 per-batch overhead — otherwise
nobody leaves it on and the flight recorder never sees the batch you
needed. This suite measures both, for both subsystems, on the same
engine shape the pipeline benchmarks use:

  untraced   ServingConfig()                        — the baseline
  traced     ServingConfig(trace=TraceConfig())     — every batch sampled
  metered    ServingConfig(telemetry=
                           TelemetryConfig())       — windowed metrics on

Rounds alternate between the deployments so clock drift and cache
warmth cancel instead of biasing one side. The traced run then exports
its chrome trace and re-validates it (every B has an E, parent refs
resolve); the metered run's exposition text is re-validated with the
in-repo Prometheus format checker.

Appends ``results/BENCH_obs.json``.

    python benchmarks/bench_obs.py [--smoke] [--requests N]
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
from repro.obs import (TelemetryConfig, TraceConfig,
                       validate_chrome_trace, validate_exposition)

OVERHEAD_BAR = 0.05          # instrumented p50 may exceed baseline by 5%
ROUNDS = 4                   # alternating measurement rounds per mode


def _drive(eng, chunks) -> list:
    """Closed-loop per-batch wall latencies (one batch in flight — the
    per-batch span cost is NOT hidden under pipelining)."""
    out = []
    for ch in chunks:
        t0 = time.perf_counter()
        eng.submit_chunk(ch).result(timeout=600)
        out.append(time.perf_counter() - t0)
    return out


def run(requests: int = 1024, batch_size: int = 8, scale: float = 0.01,
        receptive_field: int = 32, zipf_a: float = 1.1, seed: int = 0,
        dataset: str = "flickr",
        trace_out: str = "results/trace_local.json") -> dict:
    import jax

    from repro.gnn.model import init_gnn

    g = get_graph(dataset, scale=scale, seed=seed)
    cfg = GNNConfig(kind="gcn", n_layers=2,
                    receptive_field=receptive_field, f_in=g.feature_dim)
    params = init_gnn(cfg, jax.random.PRNGKey(seed))
    traffic = zipf_traffic(g, requests, zipf_a, seed + 1)
    chunks = [traffic[i:i + batch_size]
              for i in range(0, len(traffic) - batch_size + 1,
                             batch_size)]
    warm = chunks[:max(8, len(chunks) // 4)]
    meas = chunks[len(warm):]
    per_round = max(1, len(meas) // ROUNDS)
    print(f"graph: V={g.num_vertices} | {len(meas)} measured batches, "
          f"C={batch_size} N={receptive_field}, {ROUNDS} alternating "
          f"rounds per mode")

    base = ServingConfig(batch_size=batch_size, num_threads=2)
    engines = {
        "untraced": DecoupledEngine(g, cfg, params=params, config=base),
        "traced": DecoupledEngine(
            g, cfg, params=params,
            config=ServingConfig(batch_size=batch_size, num_threads=2,
                                 trace=TraceConfig())),
        "metered": DecoupledEngine(
            g, cfg, params=params,
            config=ServingConfig(batch_size=batch_size, num_threads=2,
                                 telemetry=TelemetryConfig())),
    }
    lat = {name: [] for name in engines}
    try:
        check = np.concatenate(chunks[:4])
        refs = {name: eng.infer(check, overlap=False).embeddings
                for name, eng in engines.items()}
        np.testing.assert_array_equal(refs["untraced"], refs["traced"])
        np.testing.assert_array_equal(refs["untraced"], refs["metered"])
        for name, eng in engines.items():       # compile + warm caches
            _drive(eng, warm)
        for r in range(ROUNDS):                 # interleave the modes
            block = meas[r * per_round:(r + 1) * per_round]
            for name, eng in engines.items():
                lat[name].extend(_drive(eng, block))
        traced = engines["traced"]
        rep = traced.trace_report()
        tree = traced.export_trace(trace_out)
        exposition = engines["metered"].metrics_text()
        n_series = exposition.count("# TYPE")
    finally:
        for eng in engines.values():
            eng.close()

    problems = validate_chrome_trace(tree)
    assert problems == [], f"chrome trace invalid: {problems[:5]}"
    expo_problems = validate_exposition(exposition)
    assert expo_problems == [], \
        f"exposition invalid: {expo_problems[:5]}"
    p = {name: {q: float(np.percentile(v, q))
                for q in (50, 90, 99)} for name, v in lat.items()}
    overhead = p["traced"][50] / p["untraced"][50] - 1.0
    m_overhead = p["metered"][50] / p["untraced"][50] - 1.0
    rows = [{"mode": name,
             "p50_ms": round(p[name][50] * 1e3, 3),
             "p90_ms": round(p[name][90] * 1e3, 3),
             "p99_ms": round(p[name][99] * 1e3, 3),
             "batches": len(lat[name])} for name in lat]
    print_table(rows, ["mode", "p50_ms", "p90_ms", "p99_ms", "batches"])
    print(f"tracing p50 overhead: {overhead:+.2%}, metrics "
          f"{m_overhead:+.2%} (bar {OVERHEAD_BAR:.0%}) | "
          f"{rep['spans']} spans recorded, ring dropped "
          f"{rep['spans_dropped']} | {n_series} metric families "
          f"exposed, format valid")
    print(f"bitwise traced == metered == untraced OK; chrome trace "
          f"valid -> {trace_out}")
    for e in rep["flight"]["slowest"][:3]:
        print(f"  flight: seq={e['meta'].get('seq')} "
              f"dur={e['dur'] * 1e3:.3f}ms spans={e['spans']}")
    assert overhead < OVERHEAD_BAR, (
        f"tracing adds {overhead:.2%} to p50 "
        f"({p['traced'][50] * 1e3:.3f}ms vs "
        f"{p['untraced'][50] * 1e3:.3f}ms); bar is {OVERHEAD_BAR:.0%}")
    assert m_overhead < OVERHEAD_BAR, (
        f"metrics add {m_overhead:.2%} to p50 "
        f"({p['metered'][50] * 1e3:.3f}ms vs "
        f"{p['untraced'][50] * 1e3:.3f}ms); bar is {OVERHEAD_BAR:.0%}")

    payload = {"rows": rows, "p50_overhead": round(overhead, 4),
               "metrics_p50_overhead": round(m_overhead, 4),
               "metric_families": n_series,
               "overhead_bar": OVERHEAD_BAR,
               "spans": rep["spans"],
               "spans_dropped": rep["spans_dropped"],
               "hists": {k: {"count": v["count"],
                             "p50": v["p50"], "p99": v["p99"]}
                         for k, v in rep["hists"].items()},
               "requests": requests, "batch_size": batch_size,
               "receptive_field": receptive_field,
               "num_vertices": g.num_vertices}
    record_trajectory(
        "obs", payload,
        regress={"traced_p50_ms": p["traced"][50] * 1e3,
                 "metered_p50_ms": p["metered"][50] * 1e3})
    return payload


def run_calibration(requests: int = 64, batch_size: int = 8,
                    scale: float = 0.004, receptive_field: int = 16,
                    seed: int = 0, dataset: str = "flickr") -> dict:
    """Per-ACK-op measured-latency table: every traced batch also runs
    the instrumented eager pass (calibrate_every=1), bucketing step
    walltimes by op x impl/mode x size. This is the measured-cost input
    the ROADMAP's cost-model dispatch wants."""
    import jax

    from repro.gnn.model import init_gnn

    g = get_graph(dataset, scale=scale, seed=seed)
    cfg = GNNConfig(kind="gcn", n_layers=2,
                    receptive_field=receptive_field, f_in=g.feature_dim)
    params = init_gnn(cfg, jax.random.PRNGKey(seed))
    traffic = zipf_traffic(g, requests, 1.1, seed + 1)
    sc = ServingConfig(batch_size=batch_size, num_threads=2,
                       trace=TraceConfig(calibrate_every=1))
    with DecoupledEngine(g, cfg, params=params, config=sc) as eng:
        eng.infer(traffic)
        rep = eng.trace_report()
    rows = rep.get("calibration", {}).get("rows", [])
    assert rows, "calibration pass produced no rows"
    for r in rows:
        r["mean_us"] = round(r.pop("mean_s") * 1e6, 1)
        r["p50_us"] = round(r.pop("p50_s") * 1e6, 1)
        r["p99_us"] = round(r.pop("p99_s") * 1e6, 1)
    print_table(rows, ["op", "mode", "size_bucket", "count",
                       "mean_us", "p50_us", "p99_us"])
    return {"rows": rows,
            "passes": rep["calibration"]["passes"]}


def run_suite(quick: bool = True):
    """benchmarks.run harness entry (quick == CI obs-smoke shape)."""
    if quick:
        payload = run(requests=512, batch_size=8, scale=0.004,
                      receptive_field=16)
    else:
        payload = run()
    payload["calibration"] = run_calibration()
    return payload


if __name__ == "__main__":
    enable_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=1024)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny graph + few requests (CI obs-smoke gate)")
    a = ap.parse_args()
    if a.smoke:
        run_suite(quick=True)
    else:
        run(requests=a.requests, batch_size=a.batch_size)
        run_calibration()
