"""The whole step's share of the chip's bf16 peak: the model's work for
the real targets served in the traced span (bench/flops.py), over the
device's busy time there."""
LAYER = "ACK program"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "latency_p50_ms"
BETTER = "higher"
MODEL_NEEDS = ("model_flops",)


def read(run):
    t = run.trace
    if t is None or t.busy_s <= 0 or t.model_flops <= 0:
        return None
    return 100.0 * t.model_flops / (
        t.busy_s * float(run.peaks["bf16_flops_per_s"]))
