"""Host time of the device function's call per batch: the feature
gather's dispatch, the pad and the jitted program's (asynchronous)
launch.
Read from the scheduler's hand-off ledger (``device.launch``)."""
from bench import ledger

LAYER = "dispatcher"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "latency_p50_ms"
BETTER = "lower"


def read(run):
    return ledger.ms_per_batch(run, "device.launch")
