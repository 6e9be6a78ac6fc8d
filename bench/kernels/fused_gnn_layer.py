"""The fused Aggregate+Transform kernel (kernels/fused_gnn.py): one
call's operations, and the arrays it needs, which are those the cell's
model uses (its ``FUSED_USES``) besides ``h``, ``b``, ``mask`` and the
output."""
from bench import flops

OPERANDS = ("adj", "h", "w_neigh", "w_self", "b", "mask")


def count(operands, out, model):
    named = dict(zip(OPERANDS, operands))
    c, n, f_in = named["h"][0]
    f_out = out[0][-1]
    uses = tuple(model.FUSED_USES)
    ops, _ = flops.fused_gnn_layer(c, n, f_in, f_out, "adj" in uses)
    return ops, [out] + [named[k] for k in ("h", "b", "mask") + uses]
