"""Operations and bytes, counted from shapes: the model's work per target
(for ``ack_step_mfu``) and each Pallas kernel's work per call (for the
kernel rooflines). Kept with the benchmark so that no later change to the
program can recount them.

Model work counts what the model needs, whatever implements it:
transforms dense over the field's real vertices at the unpadded widths
(2 k f_in f_out), aggregation over the induced subgraph's real edges plus
self loops (2 (E + k) f), GAT's score terms and its softmax per head and
edge. Padding, whether of vertices, feature columns or repeated targets,
counts nothing.

Kernel work counts the call as traced: operations of every grid step at
the call's shapes, and the bytes the call must move through HBM at the
least (each input it uses and the output once, unless the compiler keeps
that array in on-chip memory). An input a call is handed but does not
use is not counted, so moving it anyway shows as a lower roofline share.
"""
from __future__ import annotations

from typing import List, Tuple

F32 = 4
# VPU work per attention score and head: add, LeakyReLU, mask, max,
# subtract, exp, sum, divide
ATTN_ELEMENTWISE = 8


def _widths(model: dict) -> List[Tuple[int, int]]:
    f_in, f = int(model["f_in"]), int(model["f_hidden"])
    return [(f_in, f)] + [(f, f)] * (int(model["n_layers"]) - 1)


def model_flops(model: dict, n_vertices: int, n_edges: int) -> float:
    """The model's operations for one target whose receptive field has
    ``n_vertices`` vertices and ``n_edges`` directed edges."""
    k, e = float(n_vertices), float(n_edges)
    kind = model["kind"]
    total = 0.0
    for fi, fo in _widths(model):
        total += 2 * k * fi * fo                       # transform
        total += 2 * (e + k) * fo                      # aggregation
        if kind == "gat":
            heads = int(model["n_heads"])
            total += 2 * 2 * k * fo                    # s_src, s_dst
            total += ATTN_ELEMENTWISE * (e + k) * heads
        elif kind != "gcn":
            raise ValueError(f"no model count for kind {kind!r}")
    return total


def fused_gnn_layer(c: int, n: int, f_in: int, f_out: int,
                    aggregate: bool) -> Tuple[float, float]:
    """(operations, bytes) of one ``fused_gnn_layer`` call: with
    ``aggregate`` act(A (H W) + b) * mask, else act(H W + b) * mask."""
    ops = c * (2.0 * n * f_in * f_out + 3.0 * n * f_out)
    moved = c * n * f_in + f_in * f_out + f_out + c * n + c * n * f_out
    if aggregate:
        ops += c * 2.0 * n * n * f_out
        moved += c * n * n
    return ops, float(moved * F32)


def gat_attention(c: int, n: int, f: int, heads: int) -> Tuple[float, float]:
    """(operations, bytes) of one ``gat_attention`` call."""
    ops = c * (2.0 * n * n * f + ATTN_ELEMENTWISE * n * n * heads)
    moved = c * n * f + 2 * c * n * heads + c * n * n + c * n * f
    return ops, float(moved * F32)


# which operands of a fused_gnn_layer call each model kind's program uses:
# GCN aggregates (A (H W_neigh)); GAT's transform only applies W_self
FUSED_USES = {"gcn": ("adj", "w_neigh"), "gat": ("w_self",)}
FUSED_OPERANDS = ("adj", "h", "w_neigh", "w_self", "b", "mask")


def _nbytes(array) -> int:
    shape, itemsize, _ = array
    n = itemsize
    for d in shape:
        n *= d
    return n


def kernel_call(kernel: str, arrays: list, model: dict) -> Tuple[float, float]:
    """(operations, bytes that must cross HBM) of one traced kernel call.
    ``arrays`` is [output, operand, ...], each [shape, item bytes, memory
    space] as the trace's op text gives them; an array the compiler keeps
    in on-chip memory (space 1) moves no HBM bytes, and an operand the
    call is handed but does not use counts nothing."""
    out, *operands = arrays
    if kernel == "fused_gnn_layer":
        named = dict(zip(FUSED_OPERANDS, operands))
        c, n, f_in = named["h"][0]
        f_out = out[0][-1]
        uses = FUSED_USES[model["kind"]]
        ops, _ = fused_gnn_layer(c, n, f_in, f_out, "adj" in uses)
        needed = [out] + [named[k] for k in ("h", "b", "mask") + uses]
    elif kernel == "gat_attention":
        z, s_src = operands[0], operands[1]
        c, n, f = z[0]
        ops, _ = gat_attention(c, n, f, s_src[0][-1])
        needed = [out] + list(operands)
    else:
        raise ValueError(f"no count for kernel {kernel!r}")
    return ops, float(sum(_nbytes(a) for a in needed if a[2] == 0))


def bound_seconds(ops: float, moved: float, peaks: dict) -> Tuple[float, str]:
    """The least time the chip could take, and which bound it is."""
    t_ops = ops / float(peaks["bf16_flops_per_s"])
    t_bytes = moved / float(peaks["hbm_bytes_per_s"])
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
