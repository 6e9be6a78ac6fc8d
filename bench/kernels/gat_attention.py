"""The GAT attention kernel (kernels/gat_attention.py): one call's
operations; it needs every operand (z, s_src, s_dst, the structure) and
its output."""
from bench import flops


def count(operands, out, model):
    z, s_src = operands[0], operands[1]
    c, n, f = z[0]
    ops, _ = flops.gat_attention(c, n, f, s_src[0][-1])
    return ops, [out] + list(operands)
