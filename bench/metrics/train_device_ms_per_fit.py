"""Device time of the EM training program (core/plan.py, jitted as
train_chains in core/parallel.py) per fit of the traced window."""
MODULE = r"jit_train_chains"


def read(ctx):
    t = ctx.get("trace")
    if t is None or not ctx.get("fits"):
        return None
    s = t.module_s(MODULE)
    return None if s is None else 1e3 * s / ctx["fits"]
