"""Real (unpadded) token draws of all completed fits of the window, over
the time from window start to the end of the last fit (host clock).
The draws are counted by bench/work.py from the configuration and the
corpus lengths."""


def read(ctx):
    if "draws" not in ctx:
        return None
    return sum(ctx["draws"].values()) / ctx["window_s"]
