"""Serve one GNN with the two-level store subsystem switched on — device
feature store (full-resident) + host neighborhood cache — against Zipf-
skewed traffic, and read the cache/transfer stats off the server report.

    python examples/serve_store.py [--requests 400] [--zipf 1.1]

The engine pins the graph's feature matrix in device memory at start, so
each batch ships an int32 slot map instead of dense [C, N, f] rows; hot
targets' PPR neighborhoods come out of the LRU cache instead of re-running
local push. ``invalidate()`` shows the graph-update hook forcing a
recompute for affected targets.
"""
import argparse
import os
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.config import ServingConfig
from repro.core.engine import DecoupledEngine
from repro.gnn.model import GNNConfig
from repro.graphs.synthetic import get_graph, zipf_traffic
from repro.serve.gnn_server import GNNServer
from repro.store import StorePolicy

enable_compile_cache(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ap = argparse.ArgumentParser()
ap.add_argument("--requests", type=int, default=400)
ap.add_argument("--batch-size", type=int, default=8)
ap.add_argument("--zipf", type=float, default=1.1)
args = ap.parse_args()

g = get_graph("flickr", scale=0.005, seed=0)
cfg = GNNConfig(kind="gcn", n_layers=2, receptive_field=32,
                f_in=g.feature_dim)
policy = StorePolicy(features="resident", nbr_cache="lru",
                     nbr_capacity=512)
engine = DecoupledEngine(g, cfg, config=ServingConfig(
    batch_size=args.batch_size, store=policy))

server = GNNServer(engine, max_wait_s=0.02)
server.start()
engine.infer(np.zeros(args.batch_size, np.int64), overlap=False)  # warm

# Zipf(a) popularity, hottest = highest degree (hub-heavy traffic)
targets = zipf_traffic(g, args.requests, a=args.zipf, seed=1)
t0 = time.perf_counter()
reqs = [server.submit(int(t)) for t in targets]
server.drain(reqs, timeout=1200)
wall = time.perf_counter() - t0
server.stop()

rep = server.report()["models"]["default"]
lat, store = rep["latency"], rep["store"]
print(f"served {args.requests} Zipf({args.zipf}) requests in {wall:.2f}s "
      f"({args.requests / wall:.0f} req/s)")
print(f"p50={lat['p50'] * 1e3:.1f}ms p99={lat['p99'] * 1e3:.1f}ms "
      f"overlap={rep['stages']['overlap']}")
print(f"nbr-cache hit rate: {store['cache_hit_rate']:.2%}  "
      f"transfer ratio: {store['transfer_ratio']:.3f} "
      f"(bytes shipped: {store['bytes_shipped'] >> 10} KiB)")
print("store:", store["features"])
print("nbr_cache:", store["nbr_cache"])

# graph-update hook: invalidating a hub forces recompute of every cached
# neighborhood that reaches it
hub = int(np.argmax(g.degrees))
dropped = engine.invalidate([hub])
print(f"\ninvalidate(hub={hub}) dropped {dropped} cached neighborhoods")
engine.close()
