"""Device time of the four-chip runner's slice program
(launch/slda_parallel.py, jitted as shard_chains) per fit of the traced
window, averaged over the chips (device trace)."""
MODULE = r"jit_shard_chains"


def read(ctx):
    t = ctx.get("trace")
    if t is None or not ctx.get("fits"):
        return None
    s = t.module_s(MODULE)
    return None if s is None else 1e3 * s / ctx["fits"]
