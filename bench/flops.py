"""Operations and bytes, counted from shapes: the arithmetic that the
model's work per target (for ``ack_step_mfu``) and each Pallas kernel's
work per call (for the kernel rooflines) are built from. Kept with the
benchmark so that no later change to the program can recount them.

Each count lives in the file that its kind or kernel brings:

  bench/models/<kind>.py      ``model_flops(model, n_vertices, n_edges)``
                              and ``FUSED_USES``
  bench/kernels/<kernel>.py   ``count(operands, out, model)``

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

import functools
import os
from typing import List, Tuple

F32 = 4
# VPU work per attention score and head: add, LeakyReLU, mask, max,
# subtract, exp, sum, divide
ATTN_ELEMENTWISE = 8
KERNELS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "kernels")


def widths(model: dict) -> List[Tuple[int, int]]:
    """(f_in, f_out) of each layer."""
    f_in, f = int(model["f_in"]), int(model["f_hidden"])
    return [(f_in, f)] + [(f, f)] * (int(model["n_layers"]) - 1)


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


def kernel_names() -> Tuple[str, ...]:
    """The Pallas kernels that have a count file, in sorted order."""
    return tuple(sorted(f[:-len(".py")] for f in os.listdir(KERNELS_DIR)
                        if f.endswith(".py")))


@functools.lru_cache(maxsize=None)
def kernel_count(kernel: str):
    """``count`` of ``bench/kernels/<kernel>.py``."""
    path = os.path.join(KERNELS_DIR, f"{kernel}.py")
    if not os.path.isfile(path):
        raise ValueError(f"no count for kernel {kernel!r}: {path} is missing")
    from bench.harness import load_module
    return load_module(path, f"bench_kernel_{kernel}").count


def _nbytes(array) -> int:
    shape, itemsize, _ = array
    n = itemsize
    for d in shape:
        n *= d
    return n


def kernel_call(kernel: str, arrays: list, model) -> Tuple[float, float]:
    """(operations, bytes that must cross HBM) of one traced kernel call.
    ``arrays`` is [output, operand, ...], each [shape, item bytes, memory
    space] as the trace's op text gives them; ``model`` is the cell's
    model module (``bench/models/<kind>.py``). The kernel's count file
    gives the operations and the arrays the call needs; of those, an
    array the compiler keeps in on-chip memory (space 1) moves no HBM
    bytes, and an operand the call is handed but does not use counts
    nothing."""
    out, *operands = arrays
    ops, needed = kernel_count(kernel)(operands, out, model)
    return ops, float(sum(_nbytes(a) for a in needed if a[2] == 0))


def bound_seconds(ops: float, moved: float, peaks: dict) -> Tuple[float, str]:
    """The least time the chip could take, and which bound it is."""
    t_ops = ops / float(peaks["bf16_flops_per_s"])
    t_bytes = moved / float(peaks["hbm_bytes_per_s"])
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
