"""95th percentile of the latency of every request due in the window,
from its due time to its answer (host clock).  A request never answered
counts with the time from its due time to the end of the drain."""
import numpy as np


def read(ctx):
    if "latency_ms" not in ctx:
        return None
    return float(np.percentile(ctx["latency_ms"], 95))
