"""From a profiler trace to the benchmark's device numbers.

`load` reads the `.xplane.pb` that `jax.profiler.trace` writes, with
`jax.profiler.ProfileData`; `Reduced` turns it into:

* device busy time: the union of the intervals in which an operation
  ran, per device, inside the traced window (the host span
  `bench.window`), averaged over the devices; the idle share is
  1 − busy / window;
* device time per XLA module, for a module-name pattern: the busy time
  that falls inside that module's executions;
* the operations that took most device time;
* the longest idle gaps, each named by the innermost `bench.*` host span
  open at its middle: what the benchmark's host side was doing while
  the device waited.

Event times are nanoseconds; results are seconds.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def op_name(name: str) -> str:
    """The op's own name: "%while.22 = (s32[]...) while(...)" → "while.22"."""
    return name.split(" = ", 1)[0].lstrip("%")


def union(starts, ends) -> tuple[np.ndarray, np.ndarray]:
    """Merge intervals into disjoint sorted ones: (starts, ends)."""
    s = np.asarray(starts, np.float64)
    e = np.asarray(ends, np.float64)
    if s.size == 0:
        return s, e
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.append(reach[idx[1:] - 1], reach[-1])


def clip(starts, ends, lo, hi):
    s = np.maximum(np.asarray(starts, np.float64), lo)
    e = np.minimum(np.asarray(ends, np.float64), hi)
    keep = e > s
    return s[keep], e[keep]


class Device:
    """One device's ops inside the window: merged busy intervals, device
    time by op name, and module executions."""

    def __init__(self, ops, modules, lo, hi):
        """ops, modules: iterables of (name, start_ns, duration_ns)."""
        names, starts, durs = [], [], []
        for n, s, d in ops:
            names.append(op_name(n))
            starts.append(s)
            durs.append(d)
        s = np.asarray(starts, np.float64)
        e = s + np.asarray(durs, np.float64)
        cs, ce = np.maximum(s, lo), np.minimum(e, hi)
        inside = ce > cs
        self.op_time = {}
        for n, t in zip((n for n, k in zip(names, inside) if k),
                        (ce - cs)[inside]):
            self.op_time[n] = self.op_time.get(n, 0.0) + t
        self.busy = union(cs[inside], ce[inside])
        lens = self.busy[1] - self.busy[0]
        self.cum = np.concatenate([[0.0], np.cumsum(lens)])
        self.modules = [(n, s, s + d) for n, s, d in modules]

    def busy_before(self, t):
        """Busy time in [-inf, t] for an array of times t."""
        t = np.asarray(t, np.float64)
        s, e = self.busy
        i = np.searchsorted(s, t, side="right") - 1
        part = np.where(i >= 0, np.clip(np.minimum(t, e[np.maximum(i, 0)])
                                        - s[np.maximum(i, 0)], 0, None), 0.0)
        return self.cum[np.maximum(i, 0)] * (i >= 0) + part

    def busy_in(self, starts, ends) -> float:
        return float(np.sum(self.busy_before(ends)
                            - self.busy_before(starts)))


def load(path: str):
    """(window span, host spans, {device: (ops, modules)}) of a trace,
    ops and modules as generators over the device's events."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans, devices = [], {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines:
                devices[plane.name] = lines
        else:
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.duration_ns)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    events = lambda line: ((e.name, e.start_ns, e.duration_ns)
                           for e in line.events)
    return {"spans": spans,
            "devices": {name: {
                "ops": events(lines[OPS_LINE]),
                "modules": (events(lines[MODULES_LINE])
                            if MODULES_LINE in lines else ())}
                for name, lines in devices.items()}}


def label(spans, t) -> str:
    """The innermost host span open at time t, or "no_host_span"."""
    best = None
    for name, s, d in spans:
        if s <= t <= s + d and name != WINDOW_SPAN \
                and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "no_host_span"


class Reduced:
    """The reductions of one trace over its window.  `trace` is what
    `load` returns, or the same built by hand: {"spans": [...],
    "devices": {name: {"ops": [...], "modules": [...]}}}."""

    def __init__(self, trace: dict):
        spans = trace["spans"]
        windows = [(s, s + d) for n, s, d in spans if n == WINDOW_SPAN]
        if not windows or not trace["devices"]:
            raise ValueError("trace holds no window span or no device")
        self.lo, self.hi = windows[0]
        self.window_s = (self.hi - self.lo) * 1e-9
        self.spans = spans
        self.devices = {name: Device(d["ops"], d["modules"], self.lo,
                                     self.hi)
                        for name, d in sorted(trace["devices"].items())}

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    @property
    def busy_s(self) -> float:
        return sum(d.cum[-1] for d in self.devices.values()) \
            * 1e-9 / self.n_devices

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_s(self, pattern: str) -> float | None:
        """Busy seconds inside modules whose name matches `pattern`,
        averaged over devices; None where no such module ran."""
        rx = re.compile(pattern)
        total, found = 0.0, False
        for dev in self.devices.values():
            mods = [(s, e) for n, s, e in dev.modules if rx.search(n)]
            if not mods:
                continue
            s, e = clip(*zip(*mods), self.lo, self.hi)
            s, e = union(s, e)
            found = found or s.size > 0
            total += dev.busy_in(s, e)
        return total * 1e-9 / self.n_devices if found else None

    def top_ops(self, n: int = 10) -> list:
        per = {}
        for dev in self.devices.values():
            for op, t in dev.op_time.items():
                per[op] = per.get(op, 0.0) + t
        top = sorted(per.items(), key=lambda kv: -kv[1])[:n]
        return [[op, t * 1e-9 / self.n_devices] for op, t in top]

    def idle_gaps(self, n: int = 10) -> list:
        """The n longest idle stretches of the first device, each named
        by the host span open at its middle."""
        s, e = next(iter(self.devices.values())).busy
        gs = np.concatenate([[self.lo], e])
        ge = np.concatenate([s, [self.hi]])
        lens = ge - gs
        top = np.argsort(-lens, kind="stable")[:n]
        return [[label(self.spans, (gs[i] + ge[i]) / 2), float(lens[i]) * 1e-9]
                for i in top if lens[i] > 0]
