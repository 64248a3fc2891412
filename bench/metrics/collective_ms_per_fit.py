"""Device time of the collective operations (all-gather, all-reduce,
all-to-all, collective-permute, reduce-scatter, in their synchronous or
start/done forms) per fit of the traced window, on the chip that spent
least in them (device trace).  A chip that reaches the gather before
the others waits inside it, and the trace counts the wait as the
gather's time; the chip that arrives last waits for nobody, so its time
is the transfer alone.  The runner's chains communicate only in the
gather before the combine, so this stays near nothing."""
import re

OPS = re.compile(r"^(all-gather|all-reduce|all-to-all|collective-permute"
                 r"|reduce-scatter)")


def chip_ns(dev) -> float:
    """One chip's device time in collective operations, in ns."""
    return sum(s for op, s in dev.op_time.items() if OPS.match(op))


def read(ctx):
    t = ctx.get("trace")
    if t is None or not ctx.get("fits"):
        return None
    times = [chip_ns(d) for d in t.devices.values()]
    if not any(times):
        return None
    return 1e-6 * min(times) / ctx["fits"]
