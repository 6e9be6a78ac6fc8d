"""Fig. 10 reproduction: latency vs batch size (GraphSAGE, Flickr-like),
batch sizes {32, 64, 128, 256, 512} (paper §5.3)."""
from __future__ import annotations

import numpy as np

from benchmarks.common import (QUICK_SCALE, enable_cache, print_table,
                               record_trajectory, timeit)
from repro.core.config import ServingConfig
from repro.core.engine import DecoupledEngine
from repro.gnn.model import GNNConfig
from repro.graphs.synthetic import get_graph


def run(quick: bool = True):
    g = get_graph("flickr", scale=QUICK_SCALE["flickr"])
    cfg = GNNConfig(kind="sage", n_layers=3, receptive_field=128,
                    f_in=g.feature_dim)
    sizes = [32, 64, 128] if quick else [32, 64, 128, 256, 512]
    rng = np.random.default_rng(0)
    rows = []
    for bs in sizes:
        with DecoupledEngine(
                g, cfg,
                config=ServingConfig(batch_size=min(bs, 64))) as eng:
            targets = rng.integers(0, g.num_vertices, size=bs)
            t = timeit(lambda: eng.infer(targets), warmup=1, iters=2)
            res = eng.infer(targets)
        rows.append({"batch": bs,
                     "latency_ms": round(t["min_s"] * 1e3, 2),
                     "ms_per_target": round(t["min_s"] * 1e3 / bs, 3),
                     "overlap": res.stats.summary()["stages"]["overlap"]})
    print_table(rows, ["batch", "latency_ms", "ms_per_target", "overlap"])
    payload = {"rows": rows, "model": cfg.display}
    record_trajectory("fig10_batch", payload)
    return payload


if __name__ == "__main__":
    enable_cache()
    run(quick=False)
