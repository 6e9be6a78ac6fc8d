"""How late the load generator submitted its requests, 99th percentile:
a starved generator must not read as a fast server."""
import numpy as np

LAYER = "load generator"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "latency_p50_ms"
BETTER = "lower"


def read(run):
    if not len(run.lag):
        return None
    return 1e3 * float(np.percentile(run.lag, 99, method="higher"))
