"""Host time of the Build stage (induced subgraphs, or row-cache hits)
per batch, on the program's own host clock."""
LAYER = "host Build"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "latency_p50_ms"
BETTER = "lower"


def read(run):
    return run.stage_ms_per_batch("build")
