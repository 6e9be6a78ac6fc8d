"""Wait of a batch between the end of its last host stage and the
dispatcher's pick-up (the dispatcher busy with earlier batches).
Read from the scheduler's hand-off ledger (``queue.dispatch``)."""
from bench import ledger

LAYER = "dispatcher"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "latency_p50_ms"
BETTER = "lower"


def read(run):
    return ledger.ms_per_batch(run, "queue.dispatch")
