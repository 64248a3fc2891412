"""Share of the roofline of the four-chip runner's sampling, on the
route it runs (the Pallas kernels on a TPU): the least time one chip
could take for its part of the window's draws (the draws over the chips;
the larger of operations over peak FLOP/s and bytes over peak HBM
bytes/s, bench/work.py) over one chip's device time in the slice program
(device trace, averaged over the chips)."""
from bench import work

MODULE = r"jit_shard_chains"


def read(ctx):
    t = ctx.get("trace")
    if t is None or "draws" not in ctx:
        return None
    s = t.module_s(MODULE)
    if not s:
        return None
    per_chip = {k: v / ctx["chips"] for k, v in ctx["draws"].items()}
    share, bound = work.roofline(per_chip, ctx["n_topics"], s, ctx["peaks"])
    ctx["notes"]["shard_gibbs_roofline_bound"] = bound
    return share
