"""The benchmark's own synthetic graph generator.

A copy of the program's ``graphs/synthetic.make_graph`` (configuration
model with power-law degrees and degree-preferential endpoints,
symmetrized and deduplicated, homophilous labels from three rounds of
majority propagation, class-centred Gaussian features), kept here so that
a later change to the program's generator cannot move the yardstick.

Two changes against the original, neither of which changes what is
generated in distribution: the label votes are summed with
``np.bincount`` instead of ``np.add.at`` (the sums are of 0/1 counts and
exact either way, only faster), and the features are drawn in float32.

The graph is returned through the program's public constructor
``repro.graphs.csr.from_edge_list``; the plain reference reads the same
``indptr``/``indices``/``features`` arrays.
"""
from __future__ import annotations

import numpy as np


def powerlaw_degrees(n: int, avg: float, power: float,
                     rng: np.random.Generator) -> np.ndarray:
    """Degree sequence ~ Pareto(power - 1) scaled to the requested mean."""
    raw = 1.0 / rng.power(power - 1.0, size=n)
    raw = np.clip(raw, 1.0, n / 4)
    deg = raw * (avg / raw.mean())
    return np.maximum(1, deg.round().astype(np.int64))


def make_edges(spec: dict):
    """(src, dst, features) of the graph that ``spec`` describes:
    ``num_vertices``, ``avg_degree`` (directed, before symmetrization),
    ``feature_dim``, ``num_classes``, ``power`` and ``seed``."""
    rng = np.random.default_rng(int(spec["seed"]))
    n = int(spec["num_vertices"])
    k = int(spec["num_classes"])
    deg = powerlaw_degrees(n, float(spec["avg_degree"]),
                           float(spec["power"]), rng)
    m = int(deg.sum())
    src = np.repeat(np.arange(n, dtype=np.int64), deg)
    p = deg.astype(np.float64) / deg.sum()
    dst = rng.choice(n, size=m, p=p).astype(np.int64)
    labels = rng.integers(0, k, size=n).astype(np.int64)
    for _ in range(3):
        # votes[v, c]: neighbours of v (either direction) labelled c, plus
        # half a vote for v's own label so that ties keep it
        votes = np.bincount(dst * k + labels[src], minlength=n * k) \
            + np.bincount(src * k + labels[dst], minlength=n * k) \
            + 0.5 * np.bincount(np.arange(n) * k + labels, minlength=n * k)
        labels = votes.reshape(n, k).argmax(1)
    f = int(spec["feature_dim"])
    centers = rng.standard_normal((k, f), dtype=np.float32)
    feats = centers[labels]
    feats += 0.5 * rng.standard_normal((n, f), dtype=np.float32)
    return src, dst, feats


def make_graph(spec: dict):
    """The program's ``CSRGraph`` of ``spec``, built by its public
    constructor (symmetrized, self loops dropped, deduplicated)."""
    from repro.graphs.csr import from_edge_list
    src, dst, feats = make_edges(spec)
    return from_edge_list(src, dst, int(spec["num_vertices"]), feats,
                          symmetrize=True, name=str(spec["dataset"]))
