"""Share of the traced window in which no operation ran on the device:
1 - (union of device op intervals) / window, from the profiler trace."""
LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "latency_p50_ms"
BETTER = "lower"


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
