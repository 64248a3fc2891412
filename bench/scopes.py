"""The program's own names in a profiler trace: device scopes and host
spans, beside the reductions of `trace_reduce`.

The program names its work where the work happens (DESIGN.md §Tracing):

* `jax.named_scope`s in its jitted programs — `gibbs_sweep`,
  `count_refresh` (with `rebuild`, `compact`, `dense` nested inside),
  `eta_solve`, `predict_sweeps`, `combine` — which XLA keeps as the
  `op_name` metadata of each HLO instruction;
* host spans (`jax.profiler.TraceAnnotation`) named `slda.*`, with
  arguments: `slda.serve.submit`, `slda.serve.pack`, `slda.serve.device`,
  `slda.serve.publish`, and `slda.fit.*` in Weighted Average.

The device's `XLA Ops` events carry only the instruction's text, so an
op's scope path comes from the HLO of its module, which the profiler
writes into the `/host:metadata` plane (one `Hlo Proto` per program,
named as the `XLA Modules` events are).  `load` reads that plane with a
minimal protobuf reader and resolves each distinct op of a module once;
`Scoped` is a `trace_reduce.Reduced` that also answers:

* `scope_s(scope)`: busy seconds in the union of the intervals of ops
  whose scope path has that component (a `while` op that holds scoped
  ops but carries no scope itself stays outside), averaged over devices;
* `span_self_s(name)`: each span's duration less what its child spans
  cover, clipped to the window, summed;
* `idle_in_spans_s(prefix)`: the first device's idle time during which a
  span with that prefix is open;
* `idle_gaps`, named by the innermost `bench.` or `slda.` span.

`READERS` holds one reader per layer metric, each `read(ctx)` as a file
under `bench/metrics/` has it, returning None where its scope or span
is missing.  Run as a script, this module runs one traced window of a
cell as `bench/run.py --trace 1` does (without the check) and prints
these metrics beside the cell's own per-layer metrics:

    python3 bench/scopes.py --workload imdb.train --seed 123
"""
from __future__ import annotations

import os
import re
import sys
import time

import numpy as np

if __name__ == "__main__":
    sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))]

from bench import trace_reduce as tr  # noqa: E402

SPAN_PREFIXES = ("bench.", "slda.")
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
REFRESH_BRANCHES = ("rebuild", "compact", "dense")

# a name-stack component, possibly wrapped by transformations:
# "vmap(dense)" is scope "dense" traced under vmap
_COMPONENT = re.compile(r"^(?:[\w.-]*\()*([^()]*)\)*$")


# --------------------------------------------------- protobuf wire format

def _varint(buf: bytes, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, i: int = 0, end: int | None = None):
    """(field number, value) of the message in buf[i:end]: an int for
    scalar fields, a (start, end) range for length-delimited ones."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield num, v


def _text(buf, rng) -> str:
    return buf[rng[0]:rng[1]].decode("utf-8", "replace")


def _ints(buf, v):
    """A repeated int field's value: one varint, or a packed range."""
    if isinstance(v, int):
        return [v]
    out, i = [], v[0]
    while i < v[1]:
        x, i = _varint(buf, i)
        out.append(x)
    return out


def _hlo_op_names(buf: bytes, rng) -> dict:
    """{instruction name: op_name} of one serialized HloProto
    (HloProto.hlo_module=1 → computations=3 (id=5) → instructions=2:
    name=1, metadata=7 → op_name=2, called_computation_ids=38).  The
    TPU compiler leaves many of the fusions it makes without metadata;
    such an instruction takes the longest op_name inside the
    computations it calls: a fusion is named by what it fused."""
    comps = {}                       # computation id → [(name, op, calls)]
    for f, mod in _fields(buf, *rng):
        if f != 1:
            continue
        for g, comp in _fields(buf, *mod):
            if g != 3:
                continue
            cid, instrs = None, []
            for h, v in _fields(buf, *comp):
                if h == 5:
                    cid = v
                elif h == 2:
                    name, path, calls = None, "", []
                    for k, w in _fields(buf, *v):
                        if k == 1:
                            name = _text(buf, w)
                        elif k == 7:
                            for m, x in _fields(buf, *w):
                                if m == 2:
                                    path = _text(buf, x)
                        elif k == 38:
                            calls += _ints(buf, w)
                    instrs.append((name, path, calls))
            comps[cid] = instrs
    longest = {}

    def inside(cid):
        if cid not in longest:
            longest[cid] = ""
            for _, path, calls in comps.get(cid, ()):
                for p in [path] + [inside(c) for c in calls]:
                    if len(p) > len(longest[cid]):
                        longest[cid] = p
        return longest[cid]

    out = {}
    for instrs in comps.values():
        for name, path, calls in instrs:
            if name is not None:
                out[name] = path or max((inside(c) for c in calls),
                                        key=len, default="")
    return out


def hlo_op_names(path: str) -> dict:
    """{module name as the `XLA Modules` events give it, e.g.
    "jit_train_chains(1234)": {instruction: op_name}} from the
    `/host:metadata` plane of an `.xplane.pb` (XSpace.planes=1; XPlane
    name=2, event_metadata=4, stat_metadata=5; XEventMetadata name=2,
    stats=5; XStat metadata_id=1, bytes_value=6)."""
    with open(path, "rb") as f:
        buf = f.read()
    for num, plane in _fields(buf):
        if num != 1:
            continue
        name, events, stat_names = None, [], {}
        for g, v in _fields(buf, *plane):
            if g == 2:
                name = _text(buf, v)
                if name != METADATA_PLANE:
                    break
            elif g in (4, 5):                   # map entries: key=1, value=2
                val = next((w for k, w in _fields(buf, *v) if k == 2), None)
                if val is None:
                    continue
                if g == 4:
                    events.append(val)
                else:
                    sm = dict(_fields(buf, *val))
                    if 1 in sm and 2 in sm:
                        stat_names[sm[1]] = _text(buf, sm[2])
        if name != METADATA_PLANE:
            continue
        out = {}
        for ev in events:
            ev_name, protos = None, []
            for k, w in _fields(buf, *ev):
                if k == 2:
                    ev_name = _text(buf, w)
                elif k == 5:
                    st = dict(_fields(buf, *w))
                    if stat_names.get(st.get(1)) == HLO_PROTO_STAT \
                            and 6 in st:
                        protos.append(st[6])
            if ev_name is not None and protos:
                out[ev_name] = _hlo_op_names(buf, protos[0])
        return out
    return {}


# ------------------------------------------------------------------ load

def load(path: str) -> dict:
    """The trace as `trace_reduce.load` gives it, and besides: host spans
    prefixed `bench.` or `slda.`, each (name, start_ns, duration_ns,
    thread, args); and `op_names`, the `hlo_op_names` of the file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans, devices = [], {}
    for plane in data.planes:
        if plane.name.startswith(tr.DEVICE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            if tr.OPS_LINE in lines:
                devices[plane.name] = lines
            continue
        for line in plane.lines:
            thread = f"{plane.name}/{line.name}"
            spans += [(e.name, e.start_ns, e.duration_ns, thread,
                       dict(e.stats)) for e in line.events
                      if e.name.startswith(SPAN_PREFIXES)]
    events = lambda line: ((e.name, e.start_ns, e.duration_ns)
                           for e in line.events)
    return {"spans": spans,
            "devices": {name: {
                "ops": events(lines[tr.OPS_LINE]),
                "modules": (events(lines[tr.MODULES_LINE])
                            if tr.MODULES_LINE in lines else ())}
                for name, lines in devices.items()},
            "op_names": hlo_op_names(path)}


def components(op_name: str) -> frozenset:
    """The names in an op_name path, each without the transformations
    that wrap it: "jit(f)/while/body/count_refresh/vmap(dense)/add"
    holds "count_refresh" and "dense"."""
    return frozenset(m.group(1) for m in map(_COMPONENT.match,
                                             op_name.split("/")) if m)


# ---------------------------------------------------------------- reduce

class Scoped(tr.Reduced):
    """`trace_reduce.Reduced` of a trace that `load` gives, or the same
    built by hand (spans of 3 to 5 fields; `op_names` may be left out),
    with the program's scopes and spans."""

    def __init__(self, trace: dict):
        self.host_spans = [tuple(s) + (None, {})[len(s) - 3:]
                           for s in trace["spans"]]
        op_names = trace.get("op_names", {})
        base, self._ops = {}, {}
        for dev, d in trace["devices"].items():
            modules = sorted(d["modules"], key=lambda m: m[1])
            code, names, starts, durs = {}, [], [], []
            for op in d["ops"]:
                names.append(code.setdefault(tr.op_name(op[0]), len(code)))
                starts.append(op[1])
                durs.append(op[2])
            short = list(code)
            base[dev] = {"ops": zip([short[c] for c in names], starts,
                                    durs), "modules": modules}
            s = np.asarray(starts, np.float64)
            self._ops[dev] = (s, s + np.asarray(durs, np.float64),
                              *self._scope_keys(op_names, modules, short,
                                                np.asarray(names, np.int64),
                                                s))
        super().__init__({"spans": [s[:3] for s in self.host_spans],
                          "devices": base})

    @staticmethod
    def _scope_keys(op_names, modules, short, names, starts):
        """(key of each op, scope components of each key): an op's key
        is its name within the module execution that holds its start,
        resolved once through that module's HLO."""
        m_start = np.asarray([m[1] for m in modules], np.float64)
        m_end = m_start + np.asarray([m[2] for m in modules], np.float64)
        idx = np.searchsorted(m_start, starts, side="right") - 1
        if modules:
            idx[(idx >= 0) & (starts >= m_end[np.maximum(idx, 0)])] = -1
        uniq, key = np.unique((idx + 1) * len(short) + names,
                              return_inverse=True)
        comps = []
        for u in uniq:
            mod, name = divmod(int(u), len(short))
            hlo = op_names.get(modules[mod - 1][0], {}) if mod else {}
            comps.append(components(hlo.get(short[name], "")))
        return key, comps

    def scope_s(self, *scopes: str) -> float | None:
        """Busy seconds of ops in any of `scopes`, averaged over devices;
        None where no op of the window carries one."""
        total, found = 0.0, False
        for s, e, key, comps in self._ops.values():
            keep = np.array([not c.isdisjoint(scopes) for c in comps],
                            bool)[key]
            cs, ce = tr.clip(s[keep], e[keep], self.lo, self.hi)
            if cs.size:
                found = True
                us, ue = tr.union(cs, ce)
                total += float(np.sum(ue - us))
        return total * 1e-9 / self.n_devices if found else None

    def _spans_named(self, test):
        return [s for s in self.host_spans
                if test(s[0]) and s[0] != tr.WINDOW_SPAN]

    def span_count(self, name: str) -> int:
        """Spans called `name` that start inside the window."""
        return sum(1 for s in self._spans_named(name.__eq__)
                   if self.lo <= s[1] < self.hi)

    def span_self_s(self, name: str) -> float | None:
        """Summed self time of the spans called `name`, clipped to the
        window: each one's duration less what the spans nested in it on
        its thread cover.  None where no such span is in the window."""
        by_thread = {}
        for s in self._spans_named(lambda n: True):
            by_thread.setdefault(s[3], []).append(s)
        total, found = 0.0, False
        for spans in by_thread.values():
            spans.sort(key=lambda s: (s[1], -s[2]))
            for i, (n, s0, d0, _, _) in enumerate(spans):
                if n != name:
                    continue
                lo, hi = max(s0, self.lo), min(s0 + d0, self.hi)
                if hi <= lo:
                    continue
                found = True
                kids = []
                for k in spans[i + 1:]:
                    if k[1] >= s0 + d0:
                        break
                    if k[1] + k[2] <= s0 + d0:
                        kids.append((k[1], k[1] + k[2]))
                covered = 0.0
                if kids:
                    ks, ke = tr.union(*tr.clip(*zip(*kids), lo, hi))
                    covered = float(np.sum(ke - ks))
                total += (hi - lo) - covered
        return total * 1e-9 if found else None

    def span_s(self, name: str) -> float:
        """Summed duration of the spans called `name`, clipped to the
        window."""
        return sum(max(0, min(s[1] + s[2], self.hi) - max(s[1], self.lo))
                   for s in self._spans_named(name.__eq__)) * 1e-9

    def longest(self, name: str) -> dict | None:
        """The longest span called `name` that starts in the window: its
        start from the window's, its duration, the first device's busy
        time in it, and the time of the spans nested in it, by name."""
        spans = [s for s in self._spans_named(name.__eq__)
                 if self.lo <= s[1] < self.hi]
        if not spans:
            return None
        _, s0, d0, thread, _ = max(spans, key=lambda s: s[2])
        nested = {}
        for k in self._spans_named(lambda n: True):
            if k[3] == thread and s0 <= k[1] and k[1] + k[2] <= s0 + d0 \
                    and k[2] < d0:
                nested[k[0]] = nested.get(k[0], 0.0) + k[2] * 1e-9
        dev = next(iter(self.devices.values()))
        return {"start_s": (s0 - self.lo) * 1e-9, "s": d0 * 1e-9,
                "device_busy_s": dev.busy_in([s0], [s0 + d0]) * 1e-9,
                "nested_s": nested}

    def spans_without_device(self, name: str) -> int:
        """Spans called `name` in the window during which the first
        device ran no op."""
        dev = next(iter(self.devices.values()))
        return sum(1 for s in self._spans_named(name.__eq__)
                   if self.lo <= s[1] < self.hi
                   and dev.busy_in([s[1]], [s[1] + s[2]]) == 0)

    def idle_s(self) -> float:
        """The first device's idle seconds in the window."""
        dev = next(iter(self.devices.values()))
        return (self.hi - self.lo) * 1e-9 - dev.cum[-1] * 1e-9

    def idle_in_spans_s(self, prefix: str) -> float | None:
        """The first device's idle seconds during which a span named
        `prefix`... is open; None where no such span is in the window."""
        spans = [(s[1], s[1] + s[2]) for s in
                 self._spans_named(lambda n: n.startswith(prefix))]
        if not spans:
            return None
        ss, se = tr.clip(*zip(*spans), self.lo, self.hi)
        if not ss.size:
            return None
        ss, se = tr.union(ss, se)
        dev = next(iter(self.devices.values()))
        open_s = float(np.sum(se - ss))
        return (open_s - dev.busy_in(ss, se)) * 1e-9


# --------------------------------------------------------------- readers

def _per(ctx, key, seconds):
    return None if seconds is None or not ctx.get(key) \
        else 1e3 * seconds / ctx[key]


def _scope_per_fit(scope):
    def read(ctx):
        t = ctx.get("trace")
        return None if t is None else _per(ctx, "fits", t.scope_s(scope))
    return read


def _span_per_dispatch(name):
    def read(ctx):
        t = ctx.get("trace")
        return None if t is None else _per(ctx, "dispatches",
                                           t.span_self_s(name))
    return read


def serve_submit_ms_per_doc(ctx):
    t = ctx.get("trace")
    if t is None:
        return None
    s = t.span_self_s("slda.serve.submit")
    n = t.span_count("slda.serve.submit")
    return None if s is None or not n else 1e3 * s / n


def serve_idle_in_service_share(ctx):
    t = ctx.get("trace")
    if t is None:
        return None
    s, idle = t.idle_in_spans_s("slda.serve."), t.idle_s()
    return None if s is None or idle <= 0 else 100.0 * s / idle


def refresh_split(trace) -> dict:
    """Device seconds of each branch of the count refresh, and of the
    refresh's ops that carry none of them (notes)."""
    out = {b: trace.scope_s(b) for b in REFRESH_BRANCHES}
    whole = trace.scope_s("count_refresh")
    out["unattributed"] = None if whole is None else \
        whole - (trace.scope_s(*REFRESH_BRANCHES) or 0.0)
    return out


#: the per-layer metrics these names give (their units, layers and the
#: end-to-end metric each moves are in PERF.md §3)
READERS = {
    "gibbs_sweep_ms_per_fit": _scope_per_fit("gibbs_sweep"),
    "count_refresh_ms_per_fit": _scope_per_fit("count_refresh"),
    "eta_solve_ms_per_fit": _scope_per_fit("eta_solve"),
    "serve_pack_ms_per_dispatch": _span_per_dispatch("slda.serve.pack"),
    "serve_publish_ms_per_dispatch": _span_per_dispatch(
        "slda.serve.publish"),
    "serve_submit_ms_per_doc": serve_submit_ms_per_doc,
    "serve_idle_in_service_share": serve_idle_in_service_share,
}


# ------------------------------------------------------------------- run

def traced_cell(workload: str, seed: int, seconds: float) -> dict:
    """One traced window of a cell, as `bench/run.py --trace 1` makes
    it (no check): the cell's per-layer metrics, the READERS, the span
    self times, and what reading the trace cost each reduction."""
    import importlib
    import shutil
    import tempfile
    from bench import run
    spec = run.cell_spec(workload)
    cell, conf = spec["cell"], spec["conf"]
    devices = run.require_chips(cell["chips"])
    # every program compiled in this process: JAX's persistent cache
    # leaves the op_name metadata out of its key, so a cached program
    # carries the scope names of whatever code compiled it first
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    peaks = run.load_json(run.HERE, "peaks.json").get(
        devices[0].device_kind)
    drv = importlib.import_module(
        f"bench.drivers.{spec['traffic']['driver']}").Driver(
        conf, spec["traffic"], seed, cell["chips"])
    drv.setup(seconds)
    setup_s = time.perf_counter() - run.T_START
    log_dir = tempfile.mkdtemp(prefix="bench_scopes_")
    try:
        rec = run.traced(drv.window, min(seconds, run.TRACED_WINDOW_S),
                         log_dir)
        xplane = tr.find_xplane(log_dir)
        t0 = time.perf_counter()
        tr.Reduced(tr.load(xplane))
        read_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        scoped = Scoped(load(xplane))
        scoped_read_s = time.perf_counter() - t0
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    context = drv.context()
    ctx = dict(rec, setup_s=setup_s, kind=drv.kind, trace=scoped,
               chips=cell["chips"], peaks=peaks, n_topics=conf["n_topics"],
               notes=context.pop("notes", {}), **context)
    metrics = {m["name"]: run.load_metric(m["name"]).read(ctx)
               for m in spec["per_layer"]}
    metrics.update({name: read(ctx) for name, read in READERS.items()})
    names = sorted({s[0] for s in scoped.host_spans})
    return {"workload": workload, "seed": seed,
            "device": devices[0].device_kind, "metrics": metrics,
            "refresh_split_s": refresh_split(scoped),
            "scopes_s": {s: scoped.scope_s(s) for s in
                         ("gibbs_sweep", "count_refresh", "eta_solve",
                          "predict_sweeps", "combine")},
            "span_s": {n: scoped.span_s(n) for n in names},
            "span_self_s": {n: scoped.span_self_s(n) for n in names},
            "span_count": {n: scoped.span_count(n) for n in names},
            "window_s": scoped.window_s, "busy_s": scoped.busy_s,
            "fits": rec.get("fits"), "dispatches": rec.get("dispatches"),
            "idle_gaps": scoped.idle_gaps(10), "top_ops": scoped.top_ops(10),
            "longest_flush": scoped.longest("bench.flush"),
            "device_spans_without_ops": scoped.spans_without_device(
                "slda.serve.device"),
            "trace_stop_s": rec.get("trace_stop_s"),
            "trace_read_s": read_s, "scoped_read_s": scoped_read_s,
            "host_pauses": ctx["notes"].get("host_pauses")}


def main(argv=None) -> int:
    import argparse
    import json
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    from bench import run
    try:
        out = traced_cell(args.workload, args.seed, args.seconds)
    except run.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
