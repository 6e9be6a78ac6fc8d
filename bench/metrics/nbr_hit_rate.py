"""Share of a batch's distinct targets whose PPR neighborhood the LRU
cache held (Select skips the push for them)."""
LAYER = "neighborhood cache"
UNIT = "ratio"
SOURCE = "program_counter"
MOVES = "latency_p50_ms"
BETTER = "higher"


def read(run):
    hits, misses = run.delta("cache_hits"), run.delta("cache_misses")
    if not hits + misses:
        return None
    return hits / (hits + misses)
