"""Device time of one run of the served ACK program (the engine's jitted
forward, as the trace's module events time it)."""
LAYER = "ACK program"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "latency_p50_ms"
BETTER = "lower"


def read(run):
    t = run.trace
    if t is None or not t.program_runs:
        return None
    return 1e3 * t.program_seconds / t.program_runs
