"""95th percentile of how late the load generator submitted each request
after its due time (host clock)."""
import numpy as np


def read(ctx):
    if "lag_ms" not in ctx:
        return None
    return float(np.percentile(ctx["lag_ms"], 95))
