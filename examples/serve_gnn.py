"""End-to-end serving driver (the paper's deployment): batched mini-batch
GNN inference requests against a trained Decoupled model, with latency
percentiles — the 'latency per batch' metric of paper §3.1/§5.3.

    PYTHONPATH=src python examples/serve_gnn.py [--requests 512]
"""
import argparse
import os
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.config import ServingConfig
from repro.core.engine import DecoupledEngine
from repro.gnn.model import GNNConfig
from repro.gnn.train import train_gnn
from repro.graphs.synthetic import get_graph
from repro.serve.gnn_server import GNNServer

enable_compile_cache(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ap = argparse.ArgumentParser()
ap.add_argument("--requests", type=int, default=256)
ap.add_argument("--batch-size", type=int, default=32)
ap.add_argument("--train-steps", type=int, default=60)
args = ap.parse_args()

g = get_graph("flickr", scale=0.03, seed=0)
cfg = GNNConfig(kind="gcn", n_layers=3, receptive_field=64,
                f_in=g.feature_dim, num_classes=7)

# the paper serves PRE-TRAINED models: train one quickly first
print(f"training {cfg.display} for {args.train_steps} steps ...")
out = train_gnn(g, cfg, steps=args.train_steps, batch_size=16, lr=2e-3)
h0, h1 = out["history"][0], out["history"][-1]
print(f"  loss {h0['loss']:.3f} -> {h1['loss']:.3f}, "
      f"acc {h0['acc']:.2f} -> {h1['acc']:.2f}")

engine = DecoupledEngine(g, cfg, params=out["params"],
                         config=ServingConfig(batch_size=args.batch_size))
server = GNNServer(engine, max_wait_s=0.02)
server.start()

print(f"submitting {args.requests} requests ...")
rng = np.random.default_rng(1)
t0 = time.perf_counter()
reqs = [server.submit(int(t))
        for t in rng.integers(0, g.num_vertices, size=args.requests)]
server.drain(reqs, timeout=600)
wall = time.perf_counter() - t0
server.stop()

p = server.stats.percentiles()
print(f"\nserved {p['n']} requests in {wall:.2f}s "
      f"({p['n']/wall:.0f} req/s)")
print(f"request latency: p50 {p['p50']*1e3:.1f} ms, "
      f"p90 {p['p90']*1e3:.1f} ms, p99 {p['p99']*1e3:.1f} ms")
print(f"batch latency mean: {p['batch_mean']*1e3:.1f} ms "
      f"({server.stats.n_batches} batches)")
pred = np.argmax(reqs[0].embedding)
print(f"sample prediction for vertex {reqs[0].target}: class {pred} "
      f"(true {g.labels[reqs[0].target]})")
