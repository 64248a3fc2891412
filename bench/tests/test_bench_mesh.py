"""The four-chip cell mdna.shard4 at a CPU size: its driver, its four
metric readers, and the planted faults that reach the runner.

The driver's runs go through the whole harness but the look for a chip,
on four virtual CPU devices in one subprocess (JAX fixes the device
count at its first use).  Of bench/faults.py, `state_unchanged` (the EM
loop of `ExecutionPlan`) and `half_batch` (`core.parallel.partition`)
reach the runner; `answer_altered` and `control` patch entries it does
not call (UNREACHED).  `keyed_control` is the precision control put
where the runner's slices look for their chain programs, and
`gather_left_out` the fault that exists only across chips: each chip
combines its own chains alone.
"""
import contextlib
import json
import os
import subprocess
import sys
import textwrap
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from bench import faults, reference
from bench import trace_reduce as tr
from bench.run import load_metric

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "mdna.shard4"
REACHING = ("state_unchanged", "half_batch")
UNREACHED = {
    "answer_altered": "it patches core.ALGORITHMS['weighted'], which the "
                      "runner does not call",
    "control": "it patches the one-key programs core.parallel."
               "_train_chains_jit, _predict_chains_jit and train_chains; "
               "the runner's slices call train_chains_keyed and "
               "predict_chains_keyed with one key per global chain, so "
               "that every layout of the M chains draws the same numbers",
}
MS = 1_000_000   # ns


@contextlib.contextmanager
def keyed_control(draw_only: bool = False):
    """The plain reference in bfloat16 (with `draw_only`, only its draws'
    weights) in place of `core.parallel.train_chains_keyed` and
    `predict_chains_keyed`, handing back float32 as the program does."""
    from repro.core import parallel
    dtype, draw = ((jnp.float32, jnp.bfloat16) if draw_only
                   else (jnp.bfloat16, None))

    def train(keys, shards, cfg):
        return None, faults._model(reference.train(
            keys, faults._flat_docs(shards), faults._hp(cfg), dtype, draw))

    def predict(keys, models, corpus, cfg):
        return reference.predict(keys, models, faults._flat_docs(corpus),
                                 faults._hp(cfg), dtype, draw)[1]
    jax.clear_caches()
    try:
        with mock.patch.object(parallel, "train_chains_keyed", train), \
                mock.patch.object(parallel, "predict_chains_keyed", predict):
            yield
    finally:
        jax.clear_caches()


@contextlib.contextmanager
def gather_left_out():
    """The runner's one exchange between chips left out: `all_gather` made
    the slice's own block, so each chip combines only its own chains and
    the combined ŷ is the first chip's, while the per-chain ŷ handed out
    (which bypass the gather) still hold all M chains."""
    def own_block(x, axis_name, *, tiled=False, **_):
        return x if tiled else x[None]
    jax.clear_caches()
    try:
        with mock.patch.object(jax.lax, "all_gather", own_block):
            yield
    finally:
        jax.clear_caches()


RUNS = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [{root!r}, os.path.join({root!r}, "src")]
    from bench import faults, run
    from bench.tests.test_bench_mesh import gather_left_out, keyed_control
    TINY = dict(n_topics=8, vocab_size=200, n_docs=320, n_train=240,
                max_len=24, n_iters=10, n_chains=8, n_pred_burnin=4,
                n_pred_samples=3, length={{"dist": "lognormal",
                "median": 8.0, "sigma": 0.75, "min": 4}})
    patches = {{"program": (), "keyed_control": (keyed_control(),),
               "gather_left_out": (gather_left_out(),)}}
    for f in {reaching!r}:
        patches[f] = (faults.planted(f, "weighted"),)
    for what, p in patches.items():
        spec = run.cell_spec({cell!r})
        spec["conf"].update(TINY)
        res = run.run({cell!r}, 2 ** 33 + 12345, 0.2, False,
                      need_chip=False, cache=False, spec=spec, patches=p)
        res["what"] = what
        print(json.dumps(res, default=str), flush=True)
""")


@pytest.fixture(scope="module")
def runs():
    """{what: result object} of one tiny run of the program, of each
    fault that reaches the runner, and of the keyed control."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    prog = RUNS.format(root=ROOT, cell=CELL, reaching=REACHING)
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=600, env=env, cwd=ROOT)
    assert res.returncode == 0, res.stderr[-3000:]
    out = [json.loads(line) for line in res.stdout.splitlines()
           if line.startswith("{")]
    return {r["what"]: r for r in out}


def test_driver_completes_a_window_and_its_check_passes(runs):
    res = runs["program"]
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["checks"]) == {"count_gap", "combine_gap", "mse_excess"}
    assert res["notes"]["chains_per_chip"] == 2
    assert res["metrics"]["gibbs_tokens_per_s"]["value"] > 0
    assert res["device"]["count"] == 4


@pytest.mark.parametrize("fault", REACHING)
def test_fault_that_reaches_the_runner_fails(runs, fault):
    assert not runs[fault]["correct"], runs[fault]["checks"]


def test_keyed_control_fails_on_count_gap(runs):
    checks = runs["keyed_control"]["checks"]
    assert not runs["keyed_control"]["correct"]
    assert checks["count_gap"]["value"] > checks["count_gap"]["limit"]


def test_gather_left_out_fails_on_combine_gap(runs):
    checks = runs["gather_left_out"]["checks"]
    assert not runs["gather_left_out"]["correct"]
    assert checks["combine_gap"]["value"] > checks["combine_gap"]["limit"]


def test_every_fault_is_reaching_or_named():
    assert set(REACHING) | set(UNREACHED) == set(faults.KINDS)
    limits = json.load(open(os.path.join(ROOT, "bench", "limits",
                                         f"{CELL}.json")))
    for fault in UNREACHED:
        assert fault in limits["_from"]


# ------------------------------------------------- the metric readers

def four_chip_trace():
    """Window [0, 100] ms on four chips, one fit, with a straggler.  Every
    chip runs module jit_shard_chains over [0, 71] ms: chip k works (a
    fusion) until 40 + 10 k ms, then waits in the all-gather until 71 ms.
    Chip 3, the last to arrive, spends 1 ms in an all-gather-start/-done
    pair of 0.5 ms each; the others 31, 21 and 11 ms in one all-gather."""
    devices = {}
    for k in range(4):
        end = (40 + 10 * k) * MS
        gather = ([("all-gather-start.1", end, MS // 2),
                   ("all-gather-done.1", end + MS // 2, MS // 2)] if k == 3
                  else [("%all-gather.7 = f32[8,40]{1,0} all-gather(f32[2,40]"
                         "{1,0} %p)", end, 71 * MS - end)])
        devices[f"/device:TPU:{k}"] = {
            "ops": [("fusion.3", 0, end)] + gather,
            "modules": [("jit_shard_chains(12)", 0, 71 * MS)]}
    return tr.Reduced({"devices": devices,
                       "spans": [("bench.window", 0, 100 * MS)]})


def ctx_of(trace, fits=1):
    return {"trace": trace, "fits": fits, "chips": 4, "n_topics": 32,
            "draws": {"train": 1_000_000, "predict": 4_000_000},
            "peaks": {"flops_per_s": 1e14, "hbm_bytes_per_s": 1e11},
            "notes": {}}


def test_shard_device_ms_per_fit_averages_the_chips():
    # a module of 71 ms on each of the 4 chips, 2 fits
    read = load_metric("shard_device_ms_per_fit").read
    assert read(ctx_of(four_chip_trace(), fits=2)) == pytest.approx(35.5)


def test_shard_gibbs_roofline_reads_one_chip_of_the_draws():
    from bench import work
    ctx = ctx_of(four_chip_trace())
    per_chip = {k: v / 4 for k, v in ctx["draws"].items()}
    t_bytes = work.bytes_moved(per_chip, 32) / 1e11
    share = load_metric("shard_gibbs_roofline").read(ctx)
    assert share == pytest.approx(100.0 * t_bytes / 0.071)
    assert ctx["notes"]["shard_gibbs_roofline_bound"] == "bytes"


def test_chip_busy_spread_is_the_range_over_the_mean():
    # every chip reads 71 ms busy, the early ones waiting in the gather;
    # less their collectives they worked 40, 50, 60, 70 ms: (70 − 40) / 55
    t = four_chip_trace()
    assert {d.cum[-1] for d in t.devices.values()} == {71 * MS}
    ctx = ctx_of(t, fits=2)
    read = load_metric("chip_busy_spread").read
    assert read(ctx) == pytest.approx(100 * 30 / 55)
    # the first chip waited 31 ms, the last 1 ms, over 2 fits
    assert ctx["notes"]["gather_wait_ms_per_fit"] == pytest.approx(15)


def test_collective_ms_per_fit_counts_every_form_of_the_gather():
    # 31, 21, 11 ms of waiting and transfer on chips 0-2; chip 3, the last
    # to arrive, 1 ms (start and done), over 2 fits
    read = load_metric("collective_ms_per_fit").read
    assert read(ctx_of(four_chip_trace(), fits=2)) == pytest.approx(0.5)


@pytest.mark.parametrize("name", ["shard_device_ms_per_fit",
                                  "shard_gibbs_roofline", "chip_busy_spread",
                                  "collective_ms_per_fit"])
def test_a_reader_that_finds_nothing_returns_none(name):
    read = load_metric(name).read
    assert read(ctx_of(None)) is None
    one = tr.Reduced({"devices": {"/device:TPU:0": {
        "ops": [("fusion.1", 0, 10 * MS)],
        "modules": [("jit_train_chains(1)", 0, 10 * MS)]}},
        "spans": [("bench.window", 0, 100 * MS)]})
    assert read(ctx_of(one)) is None
