"""Share of the traced window in which no operation ran on the device
(device trace), in the serving cells."""


def read(ctx):
    if ctx.get("kind") != "serve" or ctx.get("trace") is None:
        return None
    return 100.0 * ctx["trace"].idle_share
