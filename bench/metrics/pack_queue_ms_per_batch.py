"""Wait of a batch between the end of its Build and the start of its
Pack station (the station busy with earlier batches).
Read from the scheduler's hand-off ledger (``queue.pack``)."""
from bench import ledger

LAYER = "Pack and transfer"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "latency_p50_ms"
BETTER = "lower"


def read(run):
    return ledger.ms_per_batch(run, "queue.pack")
