"""Device time of the service's dispatch program (serving/slda_service.py)
per dispatch of the traced window (device trace over the service's own
dispatch counter)."""
MODULE = r"jit_dispatch"


def read(ctx):
    t = ctx.get("trace")
    if t is None or not ctx.get("dispatches"):
        return None
    s = t.module_s(MODULE)
    return None if s is None else 1e3 * s / ctx["dispatches"]
