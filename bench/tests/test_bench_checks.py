"""The numbers of bench/checks.py on hand-made inputs, and the host-pause
record of the serving driver."""
import numpy as np
import pytest

from bench import checks
from bench.drivers.serve import HostPauses

BETA = 0.01


def phi_of(ntw, denominator=None):
    """φ̂ [1, T, W] of whole counts ntw [T, W], with the topic totals as
    the denominator unless one is given."""
    ntw = np.asarray(ntw, np.float64)
    nt = ntw.sum(-1, keepdims=True) if denominator is None \
        else np.asarray(denominator, np.float64)[:, None]
    return ((ntw + BETA) / (nt + ntw.shape[-1] * BETA))[None]


NTW = [[3, 0, 5, 1], [0, 7, 0, 2]]
WORDS = np.asarray(NTW).sum(0)[None]


def test_count_gap_of_whole_counts_is_rounding():
    assert checks.count_gap(phi_of(NTW), WORDS, BETA) < 1e-9


@pytest.mark.parametrize("what,expect", [
    ("fraction", 0.25),       # one count read back a quarter off
    ("word", 1.0),            # a word counted once too often
    ("denominator", 2.0),     # a topic total two off its counts
])
def test_count_gap_names_each_fault(what, expect):
    ntw = np.asarray(NTW, np.float64)
    phi, words = phi_of(ntw), WORDS
    if what == "fraction":
        ntw[0, 2] += 0.25
        phi = phi_of(ntw, ntw.sum(-1) - 0.25 * np.array([1, 0]))
    elif what == "word":
        words = WORDS.copy()
        words[0, 0] -= 1
    else:
        phi = phi_of(ntw, ntw.sum(-1) + np.array([2, 0]))
    assert checks.count_gap(phi, words, BETA) == pytest.approx(expect,
                                                               abs=1e-6)


def test_zbar_gap_of_sample_averages():
    s, lens = 3, np.array([4, 2])
    counts = np.array([[[5, 7]], [[6, 0]]], np.float64)    # S·L each
    zbar = counts / (s * lens[:, None, None])
    assert checks.zbar_gap(zbar, lens, s) < 1e-12
    zbar[0, 0, 0] += 0.25 / (s * 4)
    assert checks.zbar_gap(zbar, lens, s) == pytest.approx(0.25)


def test_judge_fails_a_missing_or_unbounded_number():
    limits = {"a": {"limit": 1.0}, "b": {"limit": 2.0}}
    assert checks.judge({"a": 0.5, "b": 2.0}, limits)[0]
    assert not checks.judge({"a": 0.5, "b": 2.5}, limits)[0]
    assert not checks.judge({"a": 0.5}, limits)[0]
    assert not checks.judge({"a": 0.5, "b": float("nan")}, limits)[0]
    assert not checks.judge({"a": 0.5, "b": 1.0, "c": 0.0}, limits)[0]


def test_host_pauses_keep_the_longest_and_every_collection():
    h = HostPauses()
    h.on_gc("start", {"generation": 2})
    h.on_gc("stop", {"generation": 2})
    h.on_gc("start", {"generation": 0})
    h.on_gc("stop", {"generation": 0})
    h.on_gc("start", {"generation": 0})
    h.on_gc("stop", {"generation": 0})
    h.longest["flush"] = 0.25
    out = h.summary()
    assert out["gc0_count"] == 2 and out["gc2_count"] == 1
    assert out["longest_flush_ms"] == 250.0
    assert out["gc2_longest_ms"] >= 0.0
