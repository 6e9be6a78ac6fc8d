"""Wait of each answered request in the server's lane, from its enqueue
until its batch enters ``submit_chunk`` (micro-batching and the lane
thread's own delay).
Read from the scheduler's hand-off ledger (``queue.lane``)."""
from bench import ledger

LAYER = "micro-batcher"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "latency_p50_ms"
BETTER = "lower"


def read(run):
    return ledger.ms_per_request(run, "queue.lane")
