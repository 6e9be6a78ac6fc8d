"""The ACK scatter-gather kernel (kernels/scatter_gather.py), as traced:
its operands are src, dst, w as [C, 1, E_pad] (E padded to a multiple of
the edge block EB) and h [C, N, F]. Per edge block and target, two
one-hot matmuls, [EB, N] @ [N, F] to gather the source rows and
[N, EB] @ [EB, F] to accumulate at the destinations, 2 EB N F each, and
the edge-weight multiply, EB F: over the call 4 C E_pad N F + C E_pad F.
It needs every operand once and its output once."""


def count(operands, out, model):
    src, dst, w, h = operands
    c, n, f = h[0]
    e_pad = src[0][-1]
    ops = 4.0 * c * e_pad * n * f + 1.0 * c * e_pad * f
    return ops, [out, src, dst, w, h]
