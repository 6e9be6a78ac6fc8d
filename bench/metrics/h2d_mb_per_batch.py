"""Bytes the Pack stage ships host to device per batch (counted from
the arrays' shapes by the program), in MB."""
LAYER = "Pack and transfer"
UNIT = "MB"
SOURCE = "program_counter"
MOVES = "latency_p50_ms"
BETTER = "lower"


def read(run):
    n = run.delta("batches")
    if not n:
        return None
    return run.delta("bytes_shipped") / n / 1e6
