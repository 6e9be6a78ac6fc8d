"""Time the dispatcher blocks in ``block_until_ready`` per batch: the
device's work on the batch that the launch did not already cover.
Read from the scheduler's hand-off ledger (``device.ready``)."""
from bench import ledger

LAYER = "device"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "latency_p50_ms"
BETTER = "lower"


def read(run):
    return ledger.ms_per_batch(run, "device.ready")
