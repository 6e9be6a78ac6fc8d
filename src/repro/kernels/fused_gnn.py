"""ACK dense (systolic) mode as a fused Pallas TPU kernel.

One GNN layer for a batch of C padded subgraphs:

    out[c] = act( alpha * A[c] @ (H[c] @ W_neigh)
                  + (H[c] @ W_self  if W_self is given)
                  + b ) * mask[c]

Both Feature Aggregation (A @ ·, the densified sparse kernel) and Feature
Transformation (· @ W) run on the MXU — the TPU-native expression of the
paper's single-module ACK: one compute unit executes every kernel, so there
is no FA/FT resource split to load-balance (paper Eq. 1 / §4.3).

Fusion detail (beyond-paper): associativity lets us compute
A @ (H @ W) instead of (A @ H) @ W, so the aggregated intermediate never
round-trips to HBM and the per-block FLOPs N·Fin·bf + N²·bf sum EXACTLY to
the unfused total across the f_out grid — zero redundant compute.

Grid: (C, f_out / BF). Per-step VMEM at N=256, Fin=512, BF=256 is ~1.8 MB
(A 256 KB, H 512 KB, W 512 KB, acc 2x256 KB) — comfortably inside VMEM, and
Mosaic double-buffers the HBM->VMEM streams across grid steps (the on-chip
analogue of the paper's double/triple buffering).

Covers GCN (W_neigh only), SAGE (+W_self), GIN (fold (1+eps)I into A on the
host: A' = A_bin + (1+eps)I, then MLP layer 2 is W_self-only with A unused).
GAT's attention kernel is kernels/gat_attention.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ACTS = {"none": lambda x: x, "relu": jax.nn.relu, "elu": jax.nn.elu}

# block_f autotune grid (obs.calib.run_block_autotune): candidate output-
# feature block widths. Each is a multiple of the 128-lane tile, because
# Mosaic refuses a lane block that is not (a 64 half-tile compiles only
# in interpret mode). bf partitions Fout COLUMNS only — every candidate
# computes each output column from the identical full-[Fin]/[N] reduction,
# so tuning block_f never changes numerics, only VMEM footprint vs grid
# parallelism. Candidates that don't divide Fout are skipped by the tuner
# (the kernel asserts Fout % bf == 0).
BLOCK_F_CANDIDATES = (128, 256, 512)


def _kernel(a_ref, h_ref, wn_ref, ws_ref, b_ref, m_ref, o_ref, *,
            act: str, use_agg: bool, use_self: bool):
    h = h_ref[0]                                   # [N, Fin]
    acc = jnp.zeros(o_ref.shape[1:], jnp.float32)  # [N, BF]
    if use_agg:
        hw = jnp.dot(h, wn_ref[...],
                     preferred_element_type=jnp.float32)      # FT (MXU)
        acc += jnp.dot(a_ref[0].astype(jnp.float32), hw,
                       preferred_element_type=jnp.float32)    # FA (MXU)
    if use_self:
        acc += jnp.dot(h, ws_ref[...], preferred_element_type=jnp.float32)
    acc += b_ref[0].astype(jnp.float32)
    out = ACTS[act](acc) * m_ref[0, 0][:, None].astype(jnp.float32)
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("act", "block_f", "interpret"))
def fused_gnn_layer(adj, h, w_neigh, w_self=None, b=None, mask=None, *,
                    act: str = "relu", block_f: int = 256,
                    interpret: bool = False):
    """adj [C,N,N]; h [C,N,Fin]; w_neigh [Fin,Fout] (or None); w_self
    [Fin,Fout] or None; b [Fout]; mask [C,N]. Returns [C,N,Fout].

    The mask travels as [C,1,N] so its blocks are (1,1,N): a block's
    last two dims must be (8,128)-aligned or whole for Mosaic, and a
    (1,N) block of [C,N] is neither."""
    C, N, Fin = h.shape
    use_agg = w_neigh is not None
    use_self = w_self is not None
    w_any = w_neigh if use_agg else w_self
    Fout = w_any.shape[1]
    bf = min(block_f, Fout)
    assert Fout % bf == 0, (Fout, bf)
    if b is None:
        b = jnp.zeros((Fout,), h.dtype)
    if mask is None:
        mask = jnp.ones((C, N), h.dtype)
    wn = w_neigh if use_agg else jnp.zeros((Fin, Fout), h.dtype)
    ws = w_self if use_self else jnp.zeros((Fin, Fout), h.dtype)

    grid = (C, Fout // bf)
    return pl.pallas_call(
        functools.partial(_kernel, act=act, use_agg=use_agg,
                          use_self=use_self),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, N, N), lambda c, j: (c, 0, 0)),       # adj
            pl.BlockSpec((1, N, Fin), lambda c, j: (c, 0, 0)),     # h
            pl.BlockSpec((Fin, bf), lambda c, j: (0, j)),          # w_neigh
            pl.BlockSpec((Fin, bf), lambda c, j: (0, j)),          # w_self
            pl.BlockSpec((1, bf), lambda c, j: (0, j)),            # b
            pl.BlockSpec((1, 1, N), lambda c, j: (c, 0, 0)),       # mask
        ],
        out_specs=pl.BlockSpec((1, N, bf), lambda c, j: (c, 0, j)),
        out_shape=jax.ShapeDtypeStruct((C, N, Fout), h.dtype),
        interpret=interpret,
    )(adj, h, wn, ws, b.reshape(1, Fout), mask.reshape(C, 1, N))
