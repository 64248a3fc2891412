"""Device time of the prediction program (core/predict.py, jitted as
predict_chains in core/parallel.py) per fit of the traced window."""
MODULE = r"jit_predict_chains"


def read(ctx):
    t = ctx.get("trace")
    if t is None or not ctx.get("fits"):
        return None
    s = t.module_s(MODULE)
    return None if s is None else 1e3 * s / ctx["fits"]
