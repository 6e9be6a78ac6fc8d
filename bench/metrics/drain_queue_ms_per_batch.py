"""Wait of a launched batch until the dispatcher starts to drain it (the
dispatcher holds it while it takes up the next batch).
Read from the scheduler's hand-off ledger (``queue.drain``)."""
from bench import ledger

LAYER = "dispatcher"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "latency_p50_ms"
BETTER = "lower"


def read(run):
    return ledger.ms_per_batch(run, "queue.drain")
