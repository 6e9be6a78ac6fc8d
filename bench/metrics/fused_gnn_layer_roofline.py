"""The fused Aggregate+Transform kernel (kernels/fused_gnn.py): least
time on the chip for its traced calls over their measured device time."""
from bench import tracing

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "latency_p50_ms"
BETTER = "higher"
KERNEL = "fused_gnn_layer"
MODEL_NEEDS = ("FUSED_USES",)


def read(run):
    if run.trace is None:
        return None
    return tracing.kernel_roofline(run.trace, KERNEL,
                                   run.cell.model_module(), run.peaks)
