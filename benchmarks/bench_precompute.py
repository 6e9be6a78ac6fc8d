"""Hybrid precompute-tier serving benchmark: fast path vs online PPR.

The precompute tier's claim is that a tier-fresh target costs a row
gather — no PPR push, no subgraph build, no device program — so its
serving latency must sit far below the online path's. This suite
measures that, plus what keeping the tier fresh costs under a stream of
edge updates:

  online   ServingConfig(precompute=None)              — the baseline
  hybrid   ServingConfig(precompute=PrecomputeConfig())— tier-routed

The deployment shape makes the two paths EXACTLY comparable (receptive
field = V, tiny ppr_eps): the hybrid engine's answers must be allclose
to the online engine's on the same Zipf traffic, and the fast-path p50
must undercut the online p50 by at least ``SPEEDUP_BAR``x. The refresh
sweep then applies edge-update bursts of increasing size and measures
the demotion footprint + drain (recompute) cost per rate, checking the
post-refresh answers equal a fresh engine built on the updated graph.

Appends ``results/BENCH_precompute.json``.

    python benchmarks/bench_precompute.py [--smoke] [--requests N]
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
from repro.precompute import PrecomputeConfig

SPEEDUP_BAR = 5.0            # fast-path p50 must be >= 5x below online
ROUNDS = 4                   # alternating measurement rounds per mode


def _drive(eng, chunks) -> list:
    """Closed-loop per-batch wall latencies (one batch in flight, so the
    fast path's skipped stages are NOT hidden under pipelining)."""
    out = []
    for ch in chunks:
        t0 = time.perf_counter()
        eng.submit_chunk(ch).result(timeout=600)
        out.append(time.perf_counter() - t0)
    return out


def _engine_pair(g, cfg, params, batch_size):
    base = dict(batch_size=batch_size, num_threads=2)
    return {
        "online": DecoupledEngine(
            g, cfg, params=params, config=ServingConfig(**base)),
        "hybrid": DecoupledEngine(
            g, cfg, params=params,
            config=ServingConfig(precompute=PrecomputeConfig(), **base)),
    }


def run(requests: int = 512, batch_size: int = 8, scale: float = 0.004,
        zipf_a: float = 1.1, seed: int = 0,
        dataset: str = "flickr") -> dict:
    """Fast-path vs online latency under Zipf traffic + equality check.

    receptive_field = V and a tiny ppr_eps make the online subgraph the
    FULL graph, so both paths compute the same function and the
    comparison is an equality check, not just a speed race."""
    import jax

    from repro.gnn.model import init_gnn

    g = get_graph(dataset, scale=scale, seed=seed)
    V = g.num_vertices
    cfg = GNNConfig(kind="sgc", n_layers=2, receptive_field=V,
                    f_in=g.feature_dim, ppr_eps=1e-9, readout="target")
    params = init_gnn(cfg, jax.random.PRNGKey(seed))
    traffic = zipf_traffic(g, requests, zipf_a, seed + 1)
    chunks = [traffic[i:i + batch_size]
              for i in range(0, len(traffic) - batch_size + 1,
                             batch_size)]
    warm = chunks[:max(4, len(chunks) // 4)]
    meas = chunks[len(warm):]
    per_round = max(1, len(meas) // ROUNDS)
    print(f"graph: V={V} | {len(meas)} measured batches, "
          f"C={batch_size} N={V} (full coverage), {ROUNDS} alternating "
          f"rounds per mode")

    engines = _engine_pair(g, cfg, params, batch_size)
    lat = {name: [] for name in engines}
    try:
        check = np.concatenate(chunks[:4])
        refs = {name: eng.infer(check, overlap=False).embeddings
                for name, eng in engines.items()}
        assert np.allclose(refs["online"], refs["hybrid"],
                           rtol=1e-4, atol=1e-5), (
            "hybrid serving diverged from online-only serving: max diff "
            f"{np.abs(refs['online'] - refs['hybrid']).max():.3e}")
        for eng in engines.values():            # compile + warm caches
            _drive(eng, warm)
        for r in range(ROUNDS):                 # interleave the modes
            block = meas[r * per_round:(r + 1) * per_round]
            for name, eng in engines.items():
                lat[name].extend(_drive(eng, block))
        rep = engines["hybrid"].precompute_report()
    finally:
        for eng in engines.values():
            eng.close()

    p = {name: {q: float(np.percentile(v, q))
                for q in (50, 90, 99)} for name, v in lat.items()}
    speedup = p["online"][50] / p["hybrid"][50]
    rows = [{"mode": name,
             "p50_ms": round(p[name][50] * 1e3, 3),
             "p90_ms": round(p[name][90] * 1e3, 3),
             "p99_ms": round(p[name][99] * 1e3, 3),
             "batches": len(lat[name])} for name in lat]
    print_table(rows, ["mode", "p50_ms", "p90_ms", "p99_ms", "batches"])
    print(f"fast-path p50 speedup: {speedup:.1f}x (bar "
          f"{SPEEDUP_BAR:.0f}x) | tier hit rate "
          f"{rep['hit_rate']:.3f}, {rep['resident']} resident rows, "
          f"{rep['tier_bytes']} bytes")
    print("hybrid allclose online-only OK")
    assert speedup >= SPEEDUP_BAR, (
        f"fast path p50 only {speedup:.1f}x below online "
        f"({p['hybrid'][50] * 1e3:.3f}ms vs "
        f"{p['online'][50] * 1e3:.3f}ms); bar is {SPEEDUP_BAR:.0f}x")

    return {"rows": rows, "p50_speedup": round(speedup, 2),
            "speedup_bar": SPEEDUP_BAR,
            "tier": {k: rep[k] for k in ("resident", "fresh", "hits",
                                         "misses", "hit_rate",
                                         "tier_bytes")},
            "requests": requests, "batch_size": batch_size,
            "num_vertices": V}


def run_refresh(rates=(1, 4, 16), batch_size: int = 8,
                scale: float = 0.004, seed: int = 0,
                dataset: str = "flickr") -> dict:
    """Refresh cost vs edge-update rate: per burst size, the demotion
    footprint (dependency-ball vertices knocked out of the tier) and the
    wall cost of recomputing them, with a correctness gate — after the
    drain, the hybrid engine's answers must equal a FRESH engine built
    on the updated graph."""
    import jax

    from repro.gnn.model import init_gnn

    rows = []
    for rate in rates:
        g = get_graph(dataset, scale=scale, seed=seed)
        V = g.num_vertices
        cfg = GNNConfig(kind="sgc", n_layers=2, receptive_field=V,
                        f_in=g.feature_dim, ppr_eps=1e-9,
                        readout="target")
        params = init_gnn(cfg, jax.random.PRNGKey(seed))
        sc = ServingConfig(batch_size=batch_size, num_threads=2,
                           precompute=PrecomputeConfig(auto_refresh=False))
        rng = np.random.default_rng(seed + rate)
        edges = [(int(u), int(v)) for u, v in
                 rng.integers(0, V, size=(rate, 2)) if u != v]
        with DecoupledEngine(g, cfg, params=params, config=sc) as eng:
            t0 = time.perf_counter()
            g.apply_edge_updates(insert=edges)
            t_demote = time.perf_counter() - t0
            demoted = eng.precompute_report()["demotions"]
            t0 = time.perf_counter()
            eng.precompute.drain()
            t_refresh = time.perf_counter() - t0
            targets = np.arange(min(4 * batch_size, V))
            got = eng.infer(targets).embeddings
        with DecoupledEngine(g, cfg, params=params,
                             config=ServingConfig(
                                 batch_size=batch_size,
                                 num_threads=2,
                                 precompute=PrecomputeConfig())) as ref:
            want = ref.infer(targets).embeddings
        assert np.allclose(want, got, rtol=1e-4, atol=1e-5), (
            f"post-refresh answers diverged from a fresh engine at "
            f"update rate {rate}")
        rows.append({"edges_per_burst": len(edges), "demoted": demoted,
                     "demote_ms": round(t_demote * 1e3, 3),
                     "refresh_ms": round(t_refresh * 1e3, 3),
                     "refresh_ms_per_vertex":
                         round(t_refresh * 1e3 / max(1, demoted), 4)})
    print_table(rows, ["edges_per_burst", "demoted", "demote_ms",
                       "refresh_ms", "refresh_ms_per_vertex"])
    print("post-refresh == fresh-build equality OK at every rate")
    return {"rows": rows}


def run_suite(quick: bool = True):
    """benchmarks.run harness entry (quick == CI precompute-smoke)."""
    if quick:
        payload = run(requests=256, batch_size=8, scale=0.004)
        payload["refresh"] = run_refresh(rates=(1, 4))
    else:
        payload = run(requests=1024, batch_size=8, scale=0.01)
        payload["refresh"] = run_refresh(rates=(1, 4, 16, 64))
    record_trajectory("precompute", payload)
    return payload


if __name__ == "__main__":
    enable_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=1024)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny graph + few requests (CI gate)")
    a = ap.parse_args()
    if a.smoke:
        run_suite(quick=True)
    else:
        payload = run(requests=a.requests, batch_size=a.batch_size,
                      scale=0.01)
        payload["refresh"] = run_refresh(rates=(1, 4, 16, 64))
        record_trajectory("precompute", payload)
