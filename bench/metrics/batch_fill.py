"""Requests served per lane batch over the batch size C: how full the
micro-batcher's batches leave (the rest is padding)."""
LAYER = "micro-batcher"
UNIT = "ratio"
SOURCE = "program_counter"
MOVES = "latency_p50_ms"
BETTER = "higher"


def read(run):
    batches = run.delta("lane_batches")
    if not batches:
        return None
    c = int(run.cell.config["serving"]["batch_size"])
    return run.delta("lane_requests") / (batches * c)
