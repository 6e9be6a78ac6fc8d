"""Serve SEVERAL GNN models from one server under one shared DSE plan —
the paper's "single accelerator configuration, many models" deployment
(§4.5; pushed further by GraphAGILE) as a runnable example.

    python examples/serve_multimodel.py [--requests 300]

Three engines (GCN, GraphSAGE, GAT) register on one graph; the server
recomputes the shared plan over the model set at each registration and
rejects models that don't fit it. Requests route by model name into
per-model micro-batchers that stream into each engine's persistent
pipeline; the report shows per-model tail latency and overlap.
"""
import argparse
import os
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.config import ServingConfig
from repro.core.engine import DecoupledEngine
from repro.gnn.model import GNNConfig
from repro.graphs.synthetic import get_graph
from repro.serve.gnn_server import GNNServer

enable_compile_cache(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ap = argparse.ArgumentParser()
ap.add_argument("--requests", type=int, default=300)
ap.add_argument("--batch-size", type=int, default=16)
args = ap.parse_args()

g = get_graph("flickr", scale=0.03, seed=0)
kinds = ("gcn", "sage", "gat")

server = GNNServer(max_wait_s=0.02)
for kind in kinds:
    cfg = GNNConfig(kind=kind, n_layers=2, receptive_field=64,
                    f_in=g.feature_dim)
    server.register(kind, graph=g, cfg=cfg,
                    config=ServingConfig(batch_size=args.batch_size))
print(f"registered {list(server.models)} under one plan: "
      f"BF={server.plan.block_f}, c_core={server.plan.c_core}, "
      f"vmem={server.plan.vmem_used >> 10} KiB")
server.start()

# precompile each model's program (a deployment would do this at startup)
for kind in kinds:
    server.engine_for(kind).infer(np.zeros(args.batch_size, np.int64),
                                  overlap=False)

rng = np.random.default_rng(1)
t0 = time.perf_counter()
reqs = [server.submit(int(t), model=str(k))
        for k, t in zip(rng.choice(kinds, args.requests),
                        rng.integers(0, g.num_vertices, args.requests))]
server.drain(reqs, timeout=1200)
wall = time.perf_counter() - t0
server.stop()

rep = server.report()
print(f"\nserved {args.requests} requests across {len(kinds)} models "
      f"in {wall:.2f}s ({args.requests / wall:.0f} req/s)")
for kind in kinds:
    m = rep["models"][kind]
    lat = m["latency"]
    print(f"  {kind:5s} n={lat['n']:4d}  p50 {lat['p50'] * 1e3:7.1f} ms  "
          f"p99 {lat['p99'] * 1e3:7.1f} ms  "
          f"overlap {m['stages']['overlap']:.2f}")
r = reqs[0]
print(f"\nsample: vertex {r.target} via {r.model} -> "
      f"embedding[:4] = {np.round(r.embedding[:4], 3)}")
