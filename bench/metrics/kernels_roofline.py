"""All Pallas kernel calls of the traced span together: their least time
on the chip (bench/flops.py against bench/peaks.json) over their measured
device time."""
from bench import tracing

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "latency_p50_ms"
BETTER = "higher"
MODEL_NEEDS = ("FUSED_USES",)


def read(run):
    t = run.trace
    if t is None:
        return None
    bound = total = 0.0
    model = run.cell.model_module()
    for kernel in t.kernel_seconds:
        share = tracing.kernel_roofline(t, kernel, model, run.peaks)
        if share is not None:
            secs = t.kernel_seconds[kernel]
            bound += share / 100.0 * secs
            total += secs
    return 100.0 * bound / total if total > 0 else None
