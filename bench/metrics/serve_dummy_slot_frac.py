"""Share of micro-batch slots dispatched empty over the window (the
service's dummy_slots and dispatches counters)."""


def read(ctx):
    if not ctx.get("dispatches"):
        return None
    return ctx["dummy_slots"] / (ctx["dispatches"] * ctx["batch_docs"])
