"""Decoupled GAT (Velickovic et al.), multi-head with concatenated heads,
on the target's receptive field: per layer

    z = (h W) * mask,  s_src = <z_head, a_src>,  s_dst = <z_head, a_dst>
    e[i, j] = LeakyReLU_0.2(s_dst[i] + s_src[j])  for j -> i or j = i
    h <- elu(softmax_j(e) z + b) * mask

then the element-wise max over the field's vertices.

Its work per target is ``model_flops`` (bench/flops.py), and the
operands of a ``fused_gnn_layer`` call that its program uses are
``FUSED_USES``: the transform applies W_self alone."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import flops

FUSED_USES = ("w_self",)


def init(key, model: dict):
    """Weights in the program's layout (``layer0`` f_in -> f_hidden,
    ``layers`` stacking the L-1 inner layers), float32: LeCun-normal W,
    attention vectors normal over sqrt(head width), small random biases."""
    f_in, f, n_layers = (int(model["f_in"]), int(model["f_hidden"]),
                         int(model["n_layers"]))
    heads = int(model["n_heads"])
    fh = f // heads
    inner = n_layers - 1
    ks = jax.random.split(key, 8)

    def layer(k, shape_w, lead=()):
        a, b, c, d = k
        return {"w": jax.random.normal(a, lead + shape_w)
                / jnp.sqrt(shape_w[0]),
                "a_src": jax.random.normal(b, lead + (heads, fh))
                / jnp.sqrt(fh),
                "a_dst": jax.random.normal(c, lead + (heads, fh))
                / jnp.sqrt(fh),
                "b": 0.1 * jax.random.normal(d, lead + (f,))}

    return {"layer0": layer(ks[:4], (f_in, f)),
            "layers": layer(ks[4:], (f, f), (inner,))}


def layers(params):
    yield params["layer0"]
    inner = params["layers"]
    for i in range(inner["w"].shape[0]):
        yield {k: v[i] for k, v in inner.items()}


def forward(params, x, model, dtype=None):
    """x: feats [C,N,f_in], struct [C,N,N] (edge j -> i), mask [C,N].
    Returns [C, f]."""
    cast = (lambda a: a.astype(dtype)) if dtype is not None else (lambda a: a)
    h, mask = cast(x["feats"]), cast(x["mask"])
    c, n, _ = h.shape
    heads = int(model["n_heads"])
    eye = jnp.eye(n, dtype=h.dtype)
    allowed = ((cast(x["struct"]) + eye) * mask[:, None, :]) > 0  # [C,N,N]
    allowed = allowed[:, None]                                     # heads
    for p in layers(params):
        f = p["w"].shape[1]
        z = jnp.einsum("cnf,fg->cng", h, cast(p["w"])) * mask[..., None]
        z4 = z.reshape(c, n, heads, f // heads)
        s_src = jnp.einsum("cnhf,hf->cnh", z4,
                           cast(p["a_src"])).transpose(0, 2, 1)
        s_dst = jnp.einsum("cnhf,hf->cnh", z4,
                           cast(p["a_dst"])).transpose(0, 2, 1)
        e = jax.nn.leaky_relu(s_dst[..., :, None] + s_src[..., None, :],
                              0.2)
        e = jnp.where(allowed, e, -jnp.inf)
        m = jnp.max(e, axis=-1, keepdims=True)
        ex = jnp.where(allowed, jnp.exp(e - jnp.where(jnp.isfinite(m), m,
                                                      0)), 0)
        attn = ex / jnp.maximum(jnp.sum(ex, axis=-1, keepdims=True), 1e-20)
        out = jnp.einsum("chij,cjhf->cihf", attn, z4).reshape(c, n, f)
        h = jax.nn.elu(out + cast(p["b"])) * mask[..., None]
    return jnp.max(jnp.where(mask[..., None] > 0, h, -jnp.inf), axis=1)


def model_flops(model: dict, n_vertices: int, n_edges: int) -> float:
    """Operations for one target whose receptive field has ``n_vertices``
    vertices and ``n_edges`` directed edges: GCN's transform and
    aggregation, the score terms and the softmax per head and edge."""
    k, e = float(n_vertices), float(n_edges)
    heads = int(model["n_heads"])
    total = 0.0
    for fi, fo in flops.widths(model):
        total += 2 * k * fi * fo                       # transform
        total += 2 * (e + k) * fo                      # aggregation
        total += 2 * 2 * k * fo                        # s_src, s_dst
        total += flops.ATTN_ELEMENTWISE * (e + k) * heads
    return total
