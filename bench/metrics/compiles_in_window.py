"""Programs compiled, or read back from the persistent cache, inside the
measured window (JAX's own compile events). Set-up should leave none."""
LAYER = "jit and compile cache"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "latency_p50_ms"
BETTER = "lower"


def read(run):
    return run.compiles
