"""Decoupled GCN (Kipf and Welling; decoupled as in the paper, section
2.3): on the target's receptive field, L layers of

    h <- relu(A_hat (h W) + b) * mask

then the element-wise max over the field's vertices. ``A_hat (h W)`` is
evaluated in that order, as the program's fused kernel does, so that
both round the same matmul operands at the configuration's precision.

Its work per target is ``model_flops`` (bench/flops.py), and the
operands of a ``fused_gnn_layer`` call that its program uses are
``FUSED_USES``: the program aggregates, A (H W_neigh)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import flops

FUSED_USES = ("adj", "w_neigh")


def init(key, model: dict):
    """Weights in the program's layout: ``layer0`` maps f_in -> f_hidden,
    ``layers`` stacks the L-1 inner layers. LeCun-normal weights and small
    random biases (so the bias path is checked too), float32."""
    f_in, f, n_layers = (int(model["f_in"]), int(model["f_hidden"]),
                         int(model["n_layers"]))
    k0, k1, k2, k3 = jax.random.split(key, 4)
    inner = n_layers - 1
    return {"layer0": {
        "w": jax.random.normal(k0, (f_in, f)) / jnp.sqrt(f_in),
        "b": 0.1 * jax.random.normal(k1, (f,))},
        "layers": {
        "w": jax.random.normal(k2, (inner, f, f)) / jnp.sqrt(f),
        "b": 0.1 * jax.random.normal(k3, (inner, f))}}


def layers(params):
    yield params["layer0"]
    inner = params["layers"]
    for i in range(inner["w"].shape[0]):
        yield {k: v[i] for k, v in inner.items()}


def forward(params, x, model, dtype=None):
    """x: feats [C,N,f_in], adj [C,N,N], mask [C,N]. Returns [C, f]."""
    cast = (lambda a: a.astype(dtype)) if dtype is not None else (lambda a: a)
    h, adj, mask = cast(x["feats"]), cast(x["adj"]), cast(x["mask"])
    for p in layers(params):
        hw = jnp.einsum("cnf,fg->cng", h, cast(p["w"]))
        z = jnp.einsum("cij,cjg->cig", adj, hw) + cast(p["b"])
        h = jax.nn.relu(z) * mask[..., None]
    return jnp.max(jnp.where(mask[..., None] > 0, h, -jnp.inf), axis=1)


def model_flops(model: dict, n_vertices: int, n_edges: int) -> float:
    """Operations for one target whose receptive field has ``n_vertices``
    vertices and ``n_edges`` directed edges."""
    k, e = float(n_vertices), float(n_edges)
    total = 0.0
    for fi, fo in flops.widths(model):
        total += 2 * k * fi * fo                       # transform
        total += 2 * (e + k) * fo                      # aggregation
    return total
