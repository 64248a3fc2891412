"""How unevenly the chips' work was spread: (largest − smallest) of the
chips' busy time less their collectives' time, over its mean, in %
(device trace).  Each chip's chains run alone until the gather, where a
chip that finished early waits for the slowest; the trace counts that
wait as busy, so the collectives are taken out.  The wait itself, the
largest less the smallest chip's collective time per fit, is noted as
`gather_wait_ms_per_fit`."""
from bench.run import load_metric

collective = load_metric("collective_ms_per_fit")


def read(ctx):
    t = ctx.get("trace")
    if t is None or t.n_devices < 2:
        return None
    gathers = [collective.chip_ns(d) for d in t.devices.values()]
    work = [d.cum[-1] - g for d, g in zip(t.devices.values(), gathers)]
    mean = sum(work) / len(work)
    if mean <= 0:
        return None
    if ctx.get("fits"):
        ctx["notes"]["gather_wait_ms_per_fit"] = \
            1e-6 * (max(gathers) - min(gathers)) / ctx["fits"]
    return 100.0 * (max(work) - min(work)) / mean
