"""Wait of a batch between admission and the start of its Select
station (the station busy with earlier batches).
Read from the scheduler's hand-off ledger (``queue.select``)."""
from bench import ledger

LAYER = "host Select"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "latency_p50_ms"
BETTER = "lower"


def read(run):
    return ledger.ms_per_batch(run, "queue.select")
