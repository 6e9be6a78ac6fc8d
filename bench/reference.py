"""The plain reference of one served request, independent of the program.

For a target vertex it recomputes every layer the served path went
through, from the graph arrays and the benchmark's own weights alone:

  Select  approximate personalized PageRank by forward push (Andersen,
          Chung and Lang), pushing every vertex whose residual is at
          least eps * degree in rounds, as the paper's INI does; the
          receptive field is the target plus its N-1 highest scores
          (``np.argpartition`` breaks ties among equal scores)
  Build   the induced subgraph on those vertices: the symmetric-normalized
          adjacency with self loops D^-1/2 (A + I) D^-1/2, and the 0/1
          structure A for attention
  Gather  the vertices' feature rows
  Model   ``bench/models/<kind>.py``: the layer equations in jax.numpy

It imports nothing of the program and takes none of its neighborhoods,
subgraphs, tables or padded weights. Batches are laid out as the program
lays them out ([C, N, ...] padded with zero rows and a mask), so that the
same XLA operations see the same shapes.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def ppr_push(indptr: np.ndarray, indices: np.ndarray, target: int,
             alpha: float, eps: float, max_rounds: int = 1000):
    """Touched vertices (in order of first touch) and their PPR
    estimates p + alpha * r for a push from ``target``."""
    v = len(indptr) - 1
    deg = np.diff(indptr)
    thresh = np.maximum(deg, 1) * eps
    p = np.zeros(v, np.float64)
    r = np.zeros(v, np.float64)
    r[target] = 1.0
    seen = np.zeros(v, bool)
    seen[target] = True
    touched = np.array([target], np.int64)
    frontier = touched
    for _ in range(max_rounds):
        active = frontier[r[frontier] >= thresh[frontier]]
        if len(active) == 0:
            break
        mass = r[active]
        p[active] += alpha * mass
        r[active] = 0.0
        counts = deg[active].astype(np.int64)
        keep = counts > 0
        active, mass, counts = active[keep], mass[keep], counts[keep]
        if len(active):
            nbrs = np.concatenate([indices[indptr[u]:indptr[u + 1]]
                                   for u in active])
            np.add.at(r, nbrs, np.repeat((1.0 - alpha) * mass / counts,
                                         counts))
            new = np.unique(nbrs)
            new = new[~seen[new]]
            seen[new] = True
            touched = np.concatenate([touched, new])
        frontier = touched[r[touched] >= thresh[touched]]
        if len(frontier) == 0:
            break
    return touched, p[touched] + alpha * r[touched]


def receptive_field(indptr, indices, target: int, n: int, alpha: float,
                    eps: float) -> np.ndarray:
    """The target followed by its n-1 highest-scored vertices."""
    verts, scores = ppr_push(indptr, indices, target, alpha, eps)
    others = verts != target
    verts, scores = verts[others], scores[others]
    if len(verts) > n - 1:
        top = np.argpartition(scores, -(n - 1))[-(n - 1):]
        verts, scores = verts[top], scores[top]
    order = np.argsort(-scores, kind="stable")
    return np.concatenate([[target], verts[order]]).astype(np.int64)


def induced(indptr, indices, nodes: np.ndarray, n_pad: int):
    """(adj_hat, struct, mask) of the subgraph induced by ``nodes``,
    padded to ``n_pad``: adj_hat[i, j] = (A + I)[i, j] / sqrt(d_i d_j)
    with d = 1 + in-degree, struct = A (edge j -> i), row = destination."""
    k = len(nodes)
    local = np.full(len(indptr) - 1, -1, np.int64)
    local[nodes] = np.arange(k)
    counts = indptr[nodes + 1] - indptr[nodes]
    heads = np.concatenate([indices[indptr[u]:indptr[u + 1]]
                            for u in nodes])
    tails = np.repeat(np.arange(k), counts)    # edge u -> w: row w, col u
    rows = local[heads]
    inside = rows >= 0
    a = np.zeros((k, k), np.float64)
    a[rows[inside], tails[inside]] = 1.0
    inv_sqrt = 1.0 / np.sqrt(1.0 + a.sum(axis=1))
    adj = (a + np.eye(k)) * inv_sqrt[:, None] * inv_sqrt[None, :]
    out_adj = np.zeros((n_pad, n_pad), np.float32)
    out_adj[:k, :k] = adj
    struct = np.zeros((n_pad, n_pad), np.float32)
    struct[:k, :k] = a
    mask = np.zeros(n_pad, np.float32)
    mask[:k] = 1.0
    return out_adj, struct, mask


def batch_inputs(graph_arrays: Dict[str, np.ndarray], targets: Sequence[int],
                 model: dict) -> Dict[str, np.ndarray]:
    """Select, Build and Gather for ``targets``: the model's inputs as
    [C, N, ...] arrays."""
    indptr, indices = graph_arrays["indptr"], graph_arrays["indices"]
    feats = graph_arrays["features"]
    n = int(model["receptive_field"])
    c = len(targets)
    out = {"feats": np.zeros((c, n, feats.shape[1]), np.float32),
           "adj": np.zeros((c, n, n), np.float32),
           "struct": np.zeros((c, n, n), np.float32),
           "mask": np.zeros((c, n), np.float32)}
    for b, t in enumerate(targets):
        nodes = receptive_field(indptr, indices, int(t), n,
                                float(model["ppr_alpha"]),
                                float(model["ppr_eps"]))
        out["adj"][b], out["struct"][b], out["mask"][b] = \
            induced(indptr, indices, nodes, n)
        out["feats"][b, :len(nodes)] = feats[nodes]
    return out


def embeddings(forward, params, graph_arrays, targets: Sequence[int],
               model: dict, batch: int,
               variants: Dict[str, tuple]) -> Dict[str, np.ndarray]:
    """Reference embeddings [len(targets), f] of each variant
    ``name -> (dtype, matmul precision)`` (None, None: float32 at the
    default precision), computed ``batch`` targets at a time (the last
    block padded with repeats and cut off again). Select, Build and Gather
    run once for all variants."""
    import jax
    import jax.numpy as jnp

    fwd = {name: (jax.jit(lambda p, x, d=dtype: forward(p, x, model, d)),
                  prec) for name, (dtype, prec) in variants.items()}
    outs: Dict[str, List[np.ndarray]] = {name: [] for name in variants}
    targets = list(targets)
    for i in range(0, len(targets), batch):
        block = targets[i:i + batch]
        padded = block + [block[-1]] * (batch - len(block))
        x = {k: jnp.asarray(v)
             for k, v in batch_inputs(graph_arrays, padded, model).items()}
        for name, (fn, prec) in fwd.items():
            with jax.default_matmul_precision(prec):
                y = np.asarray(fn(params, x), np.float32)
            outs[name].append(y[:len(block)])
    return {name: np.concatenate(o, axis=0) for name, o in outs.items()}


def relative_gap(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per row: max |got - want| over max |want| (how far an embedding is
    from the reference, on the scale of the reference's largest value);
    a row that is not finite reads infinite."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    gap = np.abs(got - want).max(axis=1) / np.maximum(
        np.abs(want).max(axis=1), 1e-30)
    return np.where(np.isfinite(got).all(axis=1), gap, np.inf)


def relative_rms_gap(got: np.ndarray, want: np.ndarray) -> float:
    """||got - want|| / ||want|| over all rows together (Frobenius): the
    typical error, where ``relative_gap`` reads the worst element."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))
