"""Time a batch's submit blocked on the scheduler's in-flight bound
(``max_inflight``) before admission.
Read from the scheduler's hand-off ledger (``queue.admit``)."""
from bench import ledger

LAYER = "scheduler admission"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "latency_p50_ms"
BETTER = "lower"


def read(run):
    return ledger.ms_per_batch(run, "queue.admit")
