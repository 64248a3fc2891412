"""bench/scopes.py: the program's scopes and spans on hand-built traces,
and the HLO op names of a real CPU trace."""
import glob

import jax
import jax.numpy as jnp
import pytest

from bench import scopes
from bench import trace_reduce as tr

MS = 1_000_000   # ns
MAIN = "/host:CPU/python"


def trace():
    """Window [0, 100] ms.  Device 0 runs module jit_t(1) [0, 60]: a
    `while` [0, 50] that holds a sweep op [0, 20] and refresh ops
    [20, 35] (compact) and [30, 45] (dense), then an η op [50, 55];
    module jit_p(2) [70, 80] holds a predict op [70, 80].  Host: a
    submit [0, 40] holding pack [10, 20], device [20, 25] and publish
    [25, 35]; a second submit [60, 62] on another thread."""
    paths = {"jit_t(1)": {
        "while.1": "jit(t)/while",
        "fusion.1": "jit(t)/while/body/gibbs_sweep/vmap(jit(_uniform))/add",
        "fusion.2": "jit(t)/while/body/count_refresh/cond/vmap(compact)/mul",
        "fusion.3": "count_refresh/cond/branch_0_fun/vmap(dense)/add",
        "fusion.4": "jit(t)/eta_solve/vmap(jit(solve))/dot"},
        "jit_p(2)": {"fusion.1": "jit(p)/predict_sweeps/while"}}
    ops = [("%while.1 = (s32[]) while(s32[] %t)", 0, 50 * MS),
           ("fusion.1", 0, 20 * MS), ("fusion.2", 20 * MS, 15 * MS),
           ("fusion.3", 30 * MS, 15 * MS), ("fusion.4", 50 * MS, 5 * MS),
           ("fusion.1", 70 * MS, 10 * MS), ("copy", 90 * MS, 5 * MS)]
    modules = [("jit_p(2)", 70 * MS, 10 * MS), ("jit_t(1)", 0, 60 * MS)]
    spans = [("bench.window", 0, 100 * MS, MAIN, {}),
             ("slda.serve.submit", 0, 40 * MS, MAIN, {"req_id": 0}),
             ("slda.serve.pack", 10 * MS, 10 * MS, MAIN, {"batch": 0}),
             ("slda.serve.device", 20 * MS, 5 * MS, MAIN, {"batch": 0}),
             ("slda.serve.publish", 25 * MS, 10 * MS, MAIN, {"batch": 0}),
             ("slda.serve.submit", 60 * MS, 2 * MS, "other", {}),
             ("bench.flush", 82 * MS, 4 * MS, MAIN, {})]
    return {"spans": spans, "op_names": paths,
            "devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}}


def test_scope_time_is_the_union_of_its_ops():
    r = scopes.Scoped(trace())
    assert r.scope_s("gibbs_sweep") == pytest.approx(0.020)
    assert r.scope_s("count_refresh") == pytest.approx(0.025)   # [20, 45]
    assert r.scope_s("compact") == pytest.approx(0.015)
    assert r.scope_s("dense") == pytest.approx(0.015)
    assert r.scope_s("eta_solve") == pytest.approx(0.005)
    # same op name in another module: its own HLO decides
    assert r.scope_s("predict_sweeps") == pytest.approx(0.010)
    # the while holds the scoped ops but carries no scope
    assert r.scope_s("while") == pytest.approx(0.060)
    assert r.scope_s("combine") is None
    assert r.scope_s("compact", "dense") == pytest.approx(0.025)


def test_scopes_stay_inside_the_window():
    t = trace()
    t["spans"][0] = ("bench.window", 25 * MS, 75 * MS, MAIN, {})
    r = scopes.Scoped(t)
    assert r.scope_s("gibbs_sweep") is None
    assert r.scope_s("count_refresh") == pytest.approx(0.020)


def test_span_self_time_leaves_out_children():
    r = scopes.Scoped(trace())
    # 40 ms less pack, device and publish (25 ms); plus 2 ms elsewhere
    assert r.span_self_s("slda.serve.submit") == pytest.approx(0.017)
    assert r.span_self_s("slda.serve.pack") == pytest.approx(0.010)
    assert r.span_count("slda.serve.submit") == 2
    assert r.span_s("slda.serve.submit") == pytest.approx(0.042)
    assert r.span_self_s("slda.serve.drain") is None


def test_idle_in_spans():
    r = scopes.Scoped(trace())
    # device 0 idles [55, 70], [80, 90], [95, 100]: 30 ms
    assert r.idle_s() == pytest.approx(0.030)
    # of it, only [60, 62] lies under a slda.serve. span
    assert r.idle_in_spans_s("slda.serve.") == pytest.approx(0.002)
    assert r.idle_in_spans_s("bench.flush") == pytest.approx(0.004)
    assert r.idle_in_spans_s("slda.fit.") is None


def test_longest_span_and_spans_without_device_work():
    r = scopes.Scoped(trace())
    top = r.longest("slda.serve.submit")
    assert top["start_s"] == 0 and top["s"] == pytest.approx(0.040)
    assert top["device_busy_s"] == pytest.approx(0.040)
    assert top["nested_s"] == pytest.approx({
        "slda.serve.pack": 0.010, "slda.serve.device": 0.005,
        "slda.serve.publish": 0.010})
    assert r.longest("slda.serve.drain") is None
    # the second submit [60, 62] falls in an idle stretch
    assert r.spans_without_device("slda.serve.submit") == 1


def test_gaps_are_named_by_program_spans():
    gaps = scopes.Scoped(trace()).idle_gaps(3)
    # idle [55, 70], [80, 90], [95, 100], each named at its middle
    assert [g[0] for g in gaps] == ["no_host_span", "bench.flush",
                                    "no_host_span"]
    t = trace()
    t["spans"].append(("slda.serve.pack", 54 * MS, 20 * MS, MAIN, {}))
    gaps = scopes.Scoped(t).idle_gaps(1)
    assert gaps[0][0] == "slda.serve.pack"


def test_bench_spans_alone_reduce_as_before():
    from bench.tests.test_bench_reduce import trace as bench_trace
    a, b = tr.Reduced(bench_trace()), scopes.Scoped(bench_trace())
    assert (a.busy_s, a.idle_share, a.window_s) == \
        (b.busy_s, b.idle_share, b.window_s)
    assert a.module_s("jit_a") == b.module_s("jit_a")
    assert a.top_ops(10) == b.top_ops(10)
    assert a.idle_gaps(10) == b.idle_gaps(10)
    assert b.scope_s("gibbs_sweep") is None


READ = scopes.READERS


def test_training_readers():
    ctx = {"trace": scopes.Scoped(trace()), "fits": 2}
    assert READ["gibbs_sweep_ms_per_fit"](ctx) == pytest.approx(10.0)
    assert READ["count_refresh_ms_per_fit"](ctx) == pytest.approx(12.5)
    assert READ["eta_solve_ms_per_fit"](ctx) == pytest.approx(2.5)
    split = scopes.refresh_split(ctx["trace"])
    assert split["rebuild"] is None
    assert split["compact"] == pytest.approx(0.015)
    assert split["unattributed"] == pytest.approx(0.0)


def test_serving_readers():
    ctx = {"trace": scopes.Scoped(trace()), "dispatches": 2}
    assert READ["serve_pack_ms_per_dispatch"](ctx) == pytest.approx(5.0)
    assert READ["serve_publish_ms_per_dispatch"](ctx) == pytest.approx(5.0)
    assert READ["serve_submit_ms_per_doc"](ctx) == pytest.approx(8.5)
    assert READ["serve_idle_in_service_share"](ctx) == \
        pytest.approx(100 * 2 / 30)


@pytest.mark.parametrize("name", sorted(READ))
def test_readers_give_none_where_nothing_is_read(name):
    from bench.tests.test_bench_reduce import trace as bench_trace
    assert READ[name]({"trace": None, "fits": 1, "dispatches": 1}) is None
    ctx = {"trace": scopes.Scoped(bench_trace()), "fits": 1,
           "dispatches": 1}
    assert READ[name](ctx) is None


def test_op_names_of_a_cpu_trace(tmp_path):
    @jax.jit
    def f(x):
        with jax.named_scope("outer_scope"):
            return jnp.sin(x).sum()

    x = jnp.ones(8)
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("slda.test", batch=3):
            f(x).block_until_ready()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                     recursive=True)[0]
    names = scopes.hlo_op_names(path)
    mod = next(m for m in names if m.startswith("jit_f("))
    assert any("outer_scope" in scopes.components(p)
               for p in names[mod].values())
    spans = scopes.load(path)["spans"]
    assert [(s[0], s[4]) for s in spans] == [("slda.test", {"batch": 3})]


def _pb(*fields):
    """A protobuf message from (field number, bytes | int) pairs."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def test_a_fusion_without_metadata_takes_the_name_it_fused():
    ins = lambda name, op="", calls=(): _pb(
        (1, name), *([(7, _pb((2, op)))] if op else []),
        *[(38, c) for c in calls])
    fused = _pb((5, 7), (2, ins("neg.1", "jit(t)/count_refresh/vmap(dense)"
                                         "/neg")),
                (2, ins("mul.1", "jit(t)/count_refresh/mul")))
    entry = _pb((5, 1), (2, ins("fusion.10", calls=(7,))),
                (2, ins("while.1", "jit(t)/while")))
    proto = _pb((1, _pb((1, "jit_t"), (3, fused), (3, entry))))
    names = scopes._hlo_op_names(proto, (0, len(proto)))
    assert names["while.1"] == "jit(t)/while"
    assert "dense" in scopes.components(names["fusion.10"])
