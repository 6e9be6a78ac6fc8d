"""Train a Decoupled GNN node classifier (produces the pre-trained weights
the paper's accelerator serves), a few hundred steps on CPU.

    PYTHONPATH=src python examples/train_gnn.py [--steps 200]
"""
import argparse
import os

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.gnn.model import GNNConfig
from repro.gnn.train import train_gnn
from repro.graphs.synthetic import get_graph

enable_compile_cache(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=200)
ap.add_argument("--model", default="sage",
                choices=["gcn", "sage", "gin", "gat"])
ap.add_argument("--layers", type=int, default=3)
ap.add_argument("--receptive-field", type=int, default=64)
args = ap.parse_args()

g = get_graph("flickr", scale=0.03, seed=0)
cfg = GNNConfig(kind=args.model, n_layers=args.layers,
                receptive_field=args.receptive_field,
                f_in=g.feature_dim, num_classes=7)
print(f"training {cfg.display} on {g.name} "
      f"({g.num_vertices} vertices) ...")
out = train_gnn(g, cfg, steps=args.steps, batch_size=16, lr=2e-3,
                eval_every=50)
hist = out["history"]
first = np.mean([h["loss"] for h in hist[:20]])
last = np.mean([h["loss"] for h in hist[-20:]])
acc = np.mean([h["acc"] for h in hist[-20:]])
print(f"\nloss {first:.3f} -> {last:.3f}; final train acc {acc:.2f}; "
      f"{out['wall_s']:.1f}s total "
      f"({out['wall_s']/len(hist)*1e3:.0f} ms/step)")
assert last < first, "training did not reduce loss"
