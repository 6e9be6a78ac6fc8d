"""Completion bookkeeping and callbacks per batch, including the lane's
copy of the answers to the host.
Read from the scheduler's hand-off ledger (``device.reply``)."""
from bench import ledger

LAYER = "micro-batcher"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "latency_p50_ms"
BETTER = "lower"


def read(run):
    return ledger.ms_per_batch(run, "device.reply")
