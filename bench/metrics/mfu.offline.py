"""The whole step's share of the chips' peak: the sampler's operations
of the window's draws (bench/work.py) over the window (host clock) times
the chips' peak FLOP/s."""
from bench import work


def read(ctx):
    if "draws" not in ctx:
        return None
    ops = work.ops(ctx["draws"], ctx["n_topics"])
    return 100.0 * ops / (ctx["window_s"] * ctx["chips"]
                          * ctx["peaks"]["flops_per_s"])
