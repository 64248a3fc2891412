"""Documents answered STATUS_OK inside the window, per second of it."""


def read(ctx):
    if "answered_in_window" not in ctx:
        return None
    return ctx["answered_in_window"] / ctx["window_s"]
