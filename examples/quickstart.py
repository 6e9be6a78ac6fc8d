"""Quickstart: low-latency mini-batch GNN inference with a Decoupled model.

    PYTHONPATH=src python examples/quickstart.py

Walks the paper's Algorithm 2/3 end to end on a synthetic Flickr-scale
graph: PPR important-neighbor identification on the host, fixed-shape
subgraph batches, and the jitted ACK inference program, with the
triple-buffered host/device pipeline hiding preparation latency.
"""
import os

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.config import ServingConfig
from repro.core.engine import DecoupledEngine
from repro.gnn.model import GNNConfig
from repro.graphs.synthetic import get_graph

enable_compile_cache(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# 1. graph (synthetic stand-in for Flickr: 500-dim features, power-law)
g = get_graph("flickr", scale=0.05, seed=0)
print(f"graph: {g.num_vertices} vertices, {g.num_edges} edges, "
      f"f_in={g.feature_dim}")

# 2. a Decoupled GraphSAGE: depth L=5 with a FIXED receptive field N=128
#    (depth and receptive field are independent — the paper's key idea)
cfg = GNNConfig(kind="sage", n_layers=5, receptive_field=128,
                f_in=g.feature_dim)

# 3. engine: host INI + subgraph build, device = one jitted ACK program
engine = DecoupledEngine(g, cfg, config=ServingConfig(batch_size=64))
print(f"model {cfg.display}; ACK mode = {engine.mode} "
      f"({engine.decision.summary}; {engine.decision.reason})")

# 4. mini-batch inference for 128 target vertices
targets = np.random.default_rng(0).integers(0, g.num_vertices, size=128)
result = engine.infer(targets)

print(f"embeddings: {result.embeddings.shape} "
      f"(finite: {np.isfinite(result.embeddings).all()})")
s = result.stats.summary()
lat = s["latency"]
print(f"latency: {lat['t_wall']*1e3:.1f} ms wall for {len(targets)} targets "
      f"({lat['t_wall']*1e6/len(targets):.0f} us/target)")
print(f"host/device overlap: {s['stages']['overlap']:.0%} of prep hidden "
      f"(t_init {lat['t_init']*1e3:.1f} ms, paper's Fig. 7 scheduling)")
