"""Wait of a batch between the end of its Select and the start of its
Build station (the station busy with earlier batches).
Read from the scheduler's hand-off ledger (``queue.build``)."""
from bench import ledger

LAYER = "host Build"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "latency_p50_ms"
BETTER = "lower"


def read(run):
    return ledger.ms_per_batch(run, "queue.build")
