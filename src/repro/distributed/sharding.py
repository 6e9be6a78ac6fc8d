"""Sharding policies: logical-axis rules for activations and path-based
PartitionSpecs for parameters, optimizer state, and decode caches.

Conventions (single-pod mesh ('data','model'); multi-pod adds 'pod'):
  * batch dims           -> ('pod','data')   (replicated if not divisible)
  * attention heads / ff hidden / vocab / experts -> 'model'
  * FSDP (>=100B archs): the non-'model' matrix dim additionally -> 'data'
  * ZeRO-1: optimizer moments get 'data' added on their largest replicated
    dim even when params don't (update shards over data, params re-gather)
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.launch.mesh import data_axes

BLOCK_KEYS = ("blocks", "dense_blocks", "enc_blocks")


def shard_devices(num_shards: int) -> list:
    """Device list backing ``num_shards`` logical feature-store shards.

    One device per shard when the host has enough. On the CPU backend,
    fewer devices means simulated shards — every table lands on the
    default device but keeps its own budget/placement accounting (the
    store's ``simulated`` flag reports which regime is active). On an
    accelerator a shortfall raises instead: stacking shards on one chip
    would silently drop the per-device capacity the shards exist for.
    The same helper keeps the store and any future mesh-based layout
    agreeing on device order."""
    devs = jax.devices()
    if len(devs) >= num_shards:
        return list(devs[:num_shards])
    if devs[0].platform != "cpu":
        raise ValueError(
            f"{num_shards} feature-store shards need {num_shards} "
            f"{devs[0].platform} devices; this host has {len(devs)}")
    return [devs[0]] * num_shards


def activation_rules(cfg: ModelConfig, mesh) -> Dict[str, Any]:
    """Logical axis -> mesh axis mapping for repro.models.common.shard()."""
    da = data_axes(mesh)
    n_model = mesh.shape["model"]

    def if_div(n, axis="model"):
        return axis if (n and n % n_model == 0) else None

    return {
        "batch": da,
        # heads stay on 'model' even when uneven (GSPMD pads); kv heads are
        # small — replicate unless they divide evenly
        "heads": "model" if cfg.n_heads else None,
        "kv_heads": if_div(cfg.n_kv_heads),
        "ff": "model",
        "vocab": "model",
        "experts": if_div(cfg.moe.num_experts) if cfg.moe else None,
        # inner-expert ff dim: shard over 'model' ONLY when experts aren't
        # (both on 'model' would be a duplicate-axis spec)
        "expert_ff": ("model" if cfg.moe and not if_div(cfg.moe.num_experts)
                      else None),
    }


def batch_spec(global_batch: int, mesh) -> P:
    da = data_axes(mesh)
    n = int(np.prod([mesh.shape[a] for a in da]))
    if global_batch % n == 0:
        return P(da)
    if global_batch % mesh.shape["data"] == 0:
        return P("data")
    return P(None)


# ---------------------------------------------------------------------------
# parameter specs

_IN_OUT = {  # name -> (spec for 2D [in, out]-style matrices)
    # attention / generic projections: [d_in, sharded_out]
    "wq": "in_out", "wk": "in_out", "wv": "in_out",
    "w_gate": "in_out", "w_up": "in_out", "w_in": "in_out",
    "in_proj": "in_out", "w_uq": "in_out",
    # output projections: [sharded_in, d_out]
    "wo": "out_in", "w_down": "out_in", "w_out": "out_in",
    "out_proj": "out_in",
}


def _param_spec(cfg: ModelConfig, name: str, shape, fsdp_axis):
    """Spec for the *unstacked* param."""
    nd = len(shape)
    if name == "embed":
        return P("model", fsdp_axis)
    if name == "lm_head":
        return P(fsdp_axis, "model")
    if name in ("pos_emb", "enc_pos_emb"):
        return P(None, None)
    if name == "router":
        return P(None, None)
    if name == "conv_w":
        return P(None, "model")
    if name in ("conv_b", "b_in", "bq", "bk", "bv"):
        return P("model")
    if name in ("w_dkv", "w_kr", "w_dq"):             # MLA down-proj [D, r]
        return P(fsdp_axis, None)
    if name in ("w_uk", "w_uv"):                      # MLA up-proj [r, H*d]
        return P(None, "model")
    if name == "proj":                                # MTP [2D, D]
        return P(fsdp_axis, None)
    kind = _IN_OUT.get(name)
    if kind and nd == 2:
        return P(fsdp_axis, "model") if kind == "in_out" \
            else P("model", fsdp_axis)
    if kind and nd == 3:                              # MoE expert stacks
        return (P("model", fsdp_axis, None) if kind == "in_out"
                else P("model", None, fsdp_axis))
    return P(*([None] * nd))                          # norms, scalars, bias


def _sanitize(spec: P, shape, mesh) -> P:
    """Drop axis assignments whose dimension doesn't divide evenly: pjit
    ARGUMENT shardings must tile exactly (constraints may pad, inputs may
    not). E.g. whisper's vocab 51865 cannot shard 16-ways."""
    if mesh is None:
        return spec
    parts = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, a in enumerate(parts):
        if a is None:
            out.append(None)
            continue
        axes = (a,) if isinstance(a, str) else tuple(a)
        n = int(np.prod([mesh.shape[x] for x in axes]))
        out.append(a if shape[dim] % n == 0 else None)
    return P(*out)


def param_pspecs(cfg: ModelConfig, params_tree, mesh=None):
    """PartitionSpec pytree matching ``params_tree`` (shapes or arrays)."""
    fsdp_axis = "data" if cfg.sharding.fsdp else None

    def visit(path, leaf):
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        stacked = any(n in BLOCK_KEYS for n in names)
        name = names[-1]
        shape = leaf.shape
        base_shape = shape[1:] if stacked else shape
        spec = _sanitize(_param_spec(cfg, name, base_shape, fsdp_axis),
                         base_shape, mesh)
        if stacked:
            spec = P(None, *spec)
        return spec

    return jax.tree_util.tree_map_with_path(visit, params_tree)


def cache_pspecs(cfg: ModelConfig, cache_tree, mesh, global_batch: int):
    """Decode-cache specs: batch over data axes; head-ish dims over model
    when divisible. Cache leaves are [L, B, ...]."""
    bs = batch_spec(global_batch, mesh)
    b_axis = bs[0] if len(bs) else None
    n_model = mesh.shape["model"]

    seq_cp = cfg.sharding.cache_seq_shard

    def visit(path, leaf):
        name = getattr(path[-1], "key", "")
        nd = len(leaf.shape)
        if name in ("k", "v", "cross_k", "cross_v"):  # [L,B,S,Kh,Dh]
            kh = leaf.shape[3]
            if kh % n_model == 0:
                return P(None, b_axis, None, "model", None)
            # context parallelism: kv-heads don't divide the model axis
            # (qwen 20H, phi3 10H) -> shard the SEQ dim instead; softmax
            # statistics cross shards as tiny all-reduces
            if seq_cp and leaf.shape[2] % n_model == 0:
                return P(None, b_axis, "model", None, None)
            return P(None, b_axis, None, None, None)
        if name in ("ckv", "kr"):                     # [L,B,S,r]
            if seq_cp and leaf.shape[2] % n_model == 0:
                return P(None, b_axis, "model", None)
            return P(None, b_axis, None, None)
        if name == "ssm":                             # [..,B,H,P,N]
            h = leaf.shape[-3]
            pre = [None] * (nd - 4)
            return P(*pre, b_axis,
                     "model" if h % n_model == 0 else None, None, None)
        if name == "conv":                            # [..,B,w,d_xbc]
            pre = [None] * (nd - 3)
            return P(*pre, b_axis, None,
                     "model" if leaf.shape[-1] % n_model == 0 else None)
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(visit, cache_tree)


def zero1_pspecs(param_specs, params_tree, mesh):
    """Moment specs: add 'data' on the largest still-replicated dim."""
    n_data = mesh.shape["data"]

    def visit(spec, leaf):
        shape = leaf.shape
        parts = list(spec) + [None] * (len(shape) - len(spec))
        if any(p == "data" or (isinstance(p, tuple) and "data" in p)
               for p in parts):
            return P(*parts)          # FSDP already shards over 'data'
        # pick largest replicated dim divisible by n_data
        cand = [(shape[i], i) for i in range(len(shape))
                if parts[i] is None and shape[i] % n_data == 0
                and shape[i] >= n_data]
        if not cand:
            return P(*parts)
        _, i = max(cand)
        parts[i] = "data"
        return P(*parts)

    return jax.tree_util.tree_map_with_path(
        lambda path, spec, leaf: visit(spec, leaf), param_specs, params_tree)


def named(tree_specs, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))
