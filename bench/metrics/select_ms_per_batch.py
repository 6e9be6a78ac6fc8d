"""Host time of the Select stage (PPR push or cache lookup) per batch,
on the program's own host clock."""
LAYER = "host Select"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "latency_p50_ms"
BETTER = "lower"


def read(run):
    return run.stage_ms_per_batch("select")
