"""Share of the roofline of the sampling programs, whichever route the
plan runs: the least time the chip could take for the window's draws
(the larger of operations over peak FLOP/s and bytes over peak HBM
bytes/s, bench/work.py) over the device time of the programs that
sample (device trace)."""
from bench import work

MODULE = r"jit_train_chains|jit_predict_chains"


def read(ctx):
    t = ctx.get("trace")
    if t is None or "draws" not in ctx:
        return None
    s = t.module_s(MODULE)
    if not s:
        return None
    share, bound = work.roofline(ctx["draws"], ctx["n_topics"], s,
                                 ctx["peaks"])
    ctx["notes"]["gibbs_roofline_bound"] = bound
    return share
