"""Share of the traced window in which no operation ran on the device,
averaged over the chips (device trace), in the offline cells."""


def read(ctx):
    if ctx.get("kind") != "offline" or ctx.get("trace") is None:
        return None
    return 100.0 * ctx["trace"].idle_share
