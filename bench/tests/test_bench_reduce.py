"""bench/trace_reduce.py on a hand-built trace."""
import pytest

from bench import trace_reduce as tr

MS = 1_000_000   # ns


def trace():
    """Window [0, 100] ms.  Device 0: module A [10, 40] with overlapping
    ops [10, 30] and [20, 40]; module B [60, 70] with op [60, 70].
    Device 1: one op [0, 50] in module A.  Host: a fit span [5, 95]
    holding a flush span [40, 60]."""
    d0 = {"ops": [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 10 * MS,
                   20 * MS), ("fusion.2", 20 * MS, 20 * MS),
                  ("copy", 60 * MS, 10 * MS), ("late", 150 * MS, 10 * MS)],
          "modules": [("jit_a(1)", 10 * MS, 30 * MS),
                      ("jit_b(2)", 60 * MS, 10 * MS)]}
    d1 = {"ops": [("fusion.1", 0, 50 * MS)],
          "modules": [("jit_a(1)", 0, 50 * MS)]}
    spans = [("bench.window", 0, 100 * MS), ("bench.fit", 5 * MS, 90 * MS),
             ("bench.flush", 40 * MS, 20 * MS)]
    return {"devices": {"/device:TPU:0": d0, "/device:TPU:1": d1},
            "spans": spans}


def test_union_merges_overlaps():
    s, e = tr.union([20, 10, 50, 60, 61], [40, 30, 60, 65, 62])
    assert s.tolist() == [10, 50] and e.tolist() == [40, 65]


def test_busy_is_a_union_clipped_to_the_window():
    r = tr.Reduced(trace())
    assert r.window_s == pytest.approx(0.1)
    # device 0: [10, 40] + [60, 70] = 40 ms; device 1: 50 ms; mean 45 ms
    assert r.busy_s == pytest.approx(0.045)
    assert r.idle_share == pytest.approx(0.55)


def test_module_attribution():
    r = tr.Reduced(trace())
    assert r.module_s(r"jit_a") == pytest.approx((0.030 + 0.050) / 2)
    assert r.module_s(r"jit_b") == pytest.approx(0.010 / 2)
    assert r.module_s(r"jit_c") is None


def test_gaps_are_named_by_the_innermost_host_span():
    r = tr.Reduced(trace())
    gaps = r.idle_gaps(10)
    # device 0 idles [0, 10], [40, 60] and [70, 100]
    assert [g[1] for g in gaps] == pytest.approx([0.030, 0.020, 0.010])
    assert [g[0] for g in gaps] == ["bench.fit", "bench.flush",
                                    "bench.fit"]


def test_op_names_are_cut_to_the_instruction():
    assert tr.op_name("%while.22 = (s32[]) while((s32[]) %t)") == "while.22"
    assert tr.op_name("copy-start.3") == "copy-start.3"


def test_top_ops_average_over_devices():
    top = dict(tr.Reduced(trace()).top_ops(10))
    assert top["fusion.1"] == pytest.approx((0.020 + 0.050) / 2)
    assert top["copy"] == pytest.approx(0.005)
    assert "late" not in top


def test_a_trace_without_a_window_is_refused():
    t = trace()
    t["spans"] = t["spans"][1:]
    with pytest.raises(ValueError):
        tr.Reduced(t)
