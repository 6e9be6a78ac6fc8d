"""Table 6 reproduction: Important Neighbor Identification overhead
(PPR local-push) in us per vertex, per dataset, single thread — plus the
8-thread batch throughput the paper's host uses."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import (QUICK_SCALE, enable_cache, print_table,
                               record_trajectory)
from repro.core.ini import ini_batch, select_important
from repro.graphs.synthetic import get_graph


def run(quick: bool = True):
    rows = []
    for ds in ("flickr", "ogbn-arxiv", "reddit"):
        g = get_graph(ds, scale=QUICK_SCALE[ds])
        rng = np.random.default_rng(1)
        targets = rng.integers(0, g.num_vertices, size=16 if quick else 64)
        t0 = time.perf_counter()
        for t in targets:
            select_important(g, int(t), 128)
        t_single = (time.perf_counter() - t0) / len(targets)
        t0 = time.perf_counter()
        ini_batch(g, targets, 128, num_threads=8)
        t_batch = (time.perf_counter() - t0) / len(targets)
        rows.append({"dataset": ds,
                     "us_per_vertex_1thread": round(t_single * 1e6, 1),
                     "us_per_vertex_8threads": round(t_batch * 1e6, 1),
                     "vertices": g.num_vertices,
                     "avg_degree": round(float(g.degrees.mean()), 1)})
    print_table(rows, ["dataset", "us_per_vertex_1thread",
                       "us_per_vertex_8threads", "vertices", "avg_degree"])
    payload = {"rows": rows}
    record_trajectory("table6_ini", payload)
    return payload


if __name__ == "__main__":
    enable_cache()
    run(quick=False)
