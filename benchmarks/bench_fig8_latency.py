"""Fig. 8 reproduction: decoupled mini-batch inference latency per batch
vs (model, L, N). Batch size 64, hidden 256 (paper §5.2).

The paper's claim being checked: latency grows ~LINEARLY in L at fixed N
(vs the coupled model's exponential growth — bench_fig3), and sub-
quadratically in N. Absolute numbers are container-CPU wall clock; the
modeled TPU-v5e latency from the DSE cost model is reported next to them.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import (QUICK_SCALE, enable_cache, print_table,
                               record_trajectory, timeit)
from repro.core.dse import TPUSpec, layer_costs
from repro.core.config import ServingConfig
from repro.core.engine import DecoupledEngine
from repro.gnn.model import GNNConfig
from repro.graphs.synthetic import get_graph


def modeled_tpu_latency(cfg: GNNConfig, batch: int) -> float:
    spec = TPUSpec()
    per_target = sum(
        max(c["t_compute"], c["t_memory"]) for c in
        [layer_costs(cfg, cfg.receptive_field, cfg.f_in, cfg.f_hidden,
                     spec, section="layer0")]
        + [layer_costs(cfg, cfg.receptive_field, cfg.f_hidden,
                       cfg.f_hidden, spec, section="inner")]
        * (cfg.n_layers - 1))
    return per_target * batch   # one chip, C sequential grid cells


def run(quick: bool = True):
    g = get_graph("flickr", scale=QUICK_SCALE["flickr"])
    batch = 64
    models = ["gcn", "sage", "gat"]
    layers = [3, 5] if quick else [3, 5, 8, 16]
    fields = [64, 128] if quick else [64, 128, 256]
    rows = []
    rng = np.random.default_rng(0)
    targets = rng.integers(0, g.num_vertices, size=batch)
    for kind in models:
        for L in layers:
            for N in fields:
                cfg = GNNConfig(kind=kind, n_layers=L, receptive_field=N,
                                f_in=g.feature_dim)
                with DecoupledEngine(
                        g, cfg,
                        config=ServingConfig(batch_size=batch)) as eng:
                    t = timeit(lambda: eng.infer(targets), warmup=1,
                               iters=2 if quick else 3)
                rows.append({
                    "model": kind, "L": L, "N": N,
                    "latency_ms": round(t["min_s"] * 1e3, 2),
                    "modeled_tpu_ms": round(
                        modeled_tpu_latency(cfg, batch) * 1e3, 4),
                })
    # linear-in-L check per (model, N)
    checks = []
    for kind in models:
        for N in fields:
            sub = [r for r in rows if r["model"] == kind and r["N"] == N]
            if len(sub) >= 2:
                l_lo, l_hi = sub[0], sub[-1]
                growth = l_hi["latency_ms"] / max(l_lo["latency_ms"], 1e-9)
                ratio_L = l_hi["L"] / l_lo["L"]
                checks.append({"model": kind, "N": N,
                               "lat_growth": round(growth, 2),
                               "L_growth": ratio_L,
                               "subexponential": growth < ratio_L ** 2})
    print_table(rows, ["model", "L", "N", "latency_ms", "modeled_tpu_ms"])
    print_table(checks, ["model", "N", "lat_growth", "L_growth",
                         "subexponential"])
    payload = {"rows": rows, "linearity": checks, "batch": batch,
               "graph": {"v": g.num_vertices, "e": g.num_edges}}
    record_trajectory("fig8_latency", payload)
    return payload


if __name__ == "__main__":
    enable_cache()
    run(quick=False)
