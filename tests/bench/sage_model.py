"""A model kind that only new files bring: GraphSAGE (Hamilton et al.,
mean aggregator), in the layout of the program's ``sage`` lowering, on
the target's receptive field: per layer

    h <- relu(h W_self + b + (D^-1 A h) W_neigh) * mask

with D^-1 A the in-neighbour mean (no self loop), then the element-wise
max over the field's vertices. The discovery test copies it into a
checkout as ``bench/models/sage.py``; ``CALLS`` records every count it
makes."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import flops

FUSED_USES = ("adj", "w_neigh", "w_self")
CALLS = []


def init(key, model: dict):
    f_in, f, n_layers = (int(model["f_in"]), int(model["f_hidden"]),
                         int(model["n_layers"]))
    ks = jax.random.split(key, 6)
    inner = n_layers - 1

    def layer(k, fi, lead=()):
        return {"w_self": jax.random.normal(k[0], lead + (fi, f))
                / jnp.sqrt(fi),
                "w_neigh": jax.random.normal(k[1], lead + (fi, f))
                / jnp.sqrt(fi),
                "b": 0.1 * jax.random.normal(k[2], lead + (f,))}

    return {"layer0": layer(ks[:3], f_in), "layers": layer(ks[3:], f, (inner,))}


def forward(params, x, model, dtype=None):
    """x: feats [C,N,f_in], struct [C,N,N] (edge j -> i), mask [C,N]."""
    cast = (lambda a: a.astype(dtype)) if dtype is not None else (lambda a: a)
    h, struct, mask = cast(x["feats"]), cast(x["struct"]), cast(x["mask"])
    mean = struct / jnp.maximum(struct.sum(axis=-1, keepdims=True), 1)
    inner = params["layers"]
    layers = [params["layer0"]] + [{k: v[i] for k, v in inner.items()}
                                   for i in range(inner["w_self"].shape[0])]
    for p in layers:
        z = jnp.einsum("cij,cjf->cif", mean, h)
        out = (jnp.einsum("cnf,fg->cng", h, cast(p["w_self"])) + cast(p["b"])
               + jnp.einsum("cnf,fg->cng", z, cast(p["w_neigh"])))
        h = jax.nn.relu(out) * mask[..., None]
    return jnp.max(jnp.where(mask[..., None] > 0, h, -jnp.inf), axis=1)


def model_flops(model: dict, n_vertices: int, n_edges: int) -> float:
    """Two transforms and the mean over the real edges, per layer."""
    CALLS.append((n_vertices, n_edges))
    k, e = float(n_vertices), float(n_edges)
    return sum(2 * 2 * k * fi * fo + 2 * e * fi
               for fi, fo in flops.widths(model))
