"""Zipf(a) popularity over a finite support, with popularity rank
following vertex degree (hubs are hot). A copy of the program's
``graphs/synthetic.zipf_traffic``: exact finite-support sampling from the
normalized 1/rank**a weights."""
from __future__ import annotations

import numpy as np


def sample(rng: np.random.Generator, degrees: np.ndarray, n: int, *,
           a: float) -> np.ndarray:
    v = len(degrees)
    probs = 1.0 / np.arange(1, v + 1, dtype=np.float64) ** a
    probs /= probs.sum()
    ranks = rng.choice(v, size=n, p=probs)
    by_degree = np.argsort(-degrees.astype(np.int64), kind="stable")
    return by_degree[ranks]
