"""Targets drawn uniformly over all vertices: cold-vertex and
batch-scoring queries, which a cache of popular neighborhoods cannot
serve."""
from __future__ import annotations

import numpy as np


def sample(rng: np.random.Generator, degrees: np.ndarray,
           n: int) -> np.ndarray:
    return rng.integers(0, len(degrees), size=n)
