"""Run the sLDA main path once on a TPU and check what comes out.

    python3 chip_smoke.py              # one chip: the phases below
    python3 chip_smoke.py --chips 4    # only the four-chip shard_map phase

One chip, at the size of the paper's MD&A experiment (D=4216 documents,
W=4238 words, T=32 topics, lognormal lengths up to 120 tokens, 3000
train / 1216 test, M=8 chains), corpus generated from `--seed`:

  1. Weighted Average through `core.ALGORITHMS["weighted"]` on the
     compiled Pallas route (backend "pallas", `tpu_custom_call` in the
     HLO), then the same call on the jnp route;
  2. one spl=1 EM sweep on both routes from the same state: the share
     of token assignments on which they agree;
  3. `train_chains` with `sweeps_per_launch > 1` (the fused train
     kernel), then `ntw`/`nt`/`ndt` rebuilt from the final `z` against
     the carried tables (exact);
  4. test documents served through `SLDAPredictionService` built from
     those models, against the offline plan path.

`--chips 4` runs `launch.slda_parallel.parallel_slda_shard_map`'s
Weighted Average with M=8 chains on a 4-device mesh (2 per device) and
on a 1-device mesh (8 per device) in this one process, reads each fit
through `return_chains=True`, and checks that the combined ŷ is equal,
that the 4-device program holds no collective but the one `all_gather`,
that each device holds its own chains, and that each chain's φ̂ comes
from whole counts adding up to its training shard (bench/checks.py
`count_gap`).

Every phase prints one JSON line.  A failed check exits non-zero after
the remaining phases ran; off a TPU the script exits at once.  The last
line on success is `{"ok": true, "device": {...}}`.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import SLDA_MDNA  # noqa: E402
from repro.core import (ALGORITHMS, build_plan,  # noqa: E402
                        counts_from_assignments, partition)
from repro.core.parallel import train_chains_keyed  # noqa: E402
from repro.data import make_slda_corpus, train_test_split  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.slda_parallel import parallel_slda_shard_map  # noqa: E402
from repro.serving import ServiceConfig, SLDAPredictionService  # noqa: E402
from repro.serving.slda_service import STATUS_OK, _combine_yhat  # noqa: E402

#: the paper's MD&A experiment (Section IV-A1) at full size
MDNA = dict(n_docs=4216, n_train=3000, doc_len=120, n_chains=8)
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")


@dataclasses.dataclass
class Smoke:
    """Collects the failed checks; phases keep running after one."""
    failed: list = dataclasses.field(default_factory=list)

    def check(self, ok, what: str):
        if not ok:
            self.failed.append(what)
            print(f"CHECK FAILED: {what}", flush=True)


def emit(phase: str, **fields):
    print(json.dumps({"phase": phase, **fields}, default=float), flush=True)


def make_data(seed: int, cfg, sizes: dict):
    corpus, _ = make_slda_corpus(
        jax.random.PRNGKey(seed), sizes["n_docs"], cfg.vocab_size,
        cfg.n_topics, sizes["doc_len"], rho=cfg.rho,
        doc_len_dist="lognormal")
    return train_test_split(corpus, sizes["n_train"])


def compile_and_run(fn, *args):
    """(outputs, compile_s, run_s, hlo text) of `jax.jit(fn)(*args)`."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    t2 = time.perf_counter()
    return out, t1 - t0, t2 - t1, compiled.as_text()


def collective_kinds(hlo: str) -> dict:
    """Count of each collective op (sync or async-start form) in HLO."""
    kinds = {}
    for op in re.findall(r"\s(" + "|".join(COLLECTIVES) + r")(?:-start)?\(",
                         hlo):
        kinds[op] = kinds.get(op, 0) + 1
    return kinds


def weighted_phase(sm: Smoke, cfg, train, test, m: int, expect: str):
    """Weighted Average end to end on the route `cfg` resolves to."""
    backend = cfg.resolve_backend()
    sm.check(backend == expect, f"weighted: backend {backend} != {expect}")
    run = functools.partial(ALGORITHMS["weighted"], cfg=cfg, m=m)
    yhat, c_s, r_s, hlo = compile_and_run(
        run, jax.random.PRNGKey(1), train, test)
    mse = float(jnp.mean((yhat - test.y) ** 2))
    r2 = 1.0 - mse / float(jnp.var(test.y))
    kernel = "tpu_custom_call" in hlo
    emit(f"weighted_{backend}", compile_s=c_s, run_s=r_s, test_mse=mse,
         r2=r2, tpu_custom_call=kernel)
    sm.check(np.isfinite(mse), f"weighted {backend}: non-finite MSE")
    sm.check(r2 >= 0.5, f"weighted {backend}: R2 {r2:.4f} < 0.5")
    if backend == "pallas":
        sm.check(kernel, "weighted pallas: no tpu_custom_call in the HLO")
    return mse


def agreement_phase(cfg, shards, m: int, routes):
    """One spl=1 sweep from the same init on both routes: the share of
    real tokens assigned the same topic."""
    one = dataclasses.replace(cfg, n_iters=1, sweeps_per_launch=1)
    keys = jax.random.split(jax.random.PRNGKey(2), m)
    z = {}
    for backend in routes:
        plan = build_plan(shards, one, backend)
        state, _ = jax.jit(lambda p, k: p.train(k))(plan, keys)
        z[backend] = np.asarray(state.z)
    mask = np.asarray(shards.mask) > 0
    share = float((z[routes[0]] == z[routes[1]])[mask].mean())
    emit("spl1_agreement", routes=list(routes), share=share)


def fused_train_phase(sm: Smoke, cfg, shards, m: int, expect: str):
    """train_chains with sweeps_per_launch>1, then exact count
    consistency of the carried tables against the final z."""
    fused = dataclasses.replace(cfg, sweeps_per_launch=4)
    sm.check(fused.resolve_backend() == expect,
             f"fused train: backend {fused.resolve_backend()} != {expect}")
    keys = jax.random.split(jax.random.PRNGKey(3), m)
    (state, models), c_s, r_s, hlo = compile_and_run(
        lambda k, s: train_chains_keyed(k, s, fused), keys, shards)
    ndt, ntw, nt = jax.vmap(lambda t, mk, z: counts_from_assignments(
        t, mk, z, cfg.n_topics, cfg.vocab_size))(
        shards.tokens, shards.mask, state.z)
    exact = {name: bool(jnp.array_equal(a, b)) for name, a, b in
             (("ntw", ntw, state.ntw), ("nt", nt, state.nt),
              ("ndt", ndt, state.ndt))}
    emit("fused_train", sweeps_per_launch=fused.sweeps_per_launch,
         compile_s=c_s, run_s=r_s,
         tpu_custom_call="tpu_custom_call" in hlo,
         train_mse=float(jnp.mean(models.train_mse)), counts_exact=exact)
    if expect == "pallas":
        sm.check("tpu_custom_call" in hlo, "fused train: no tpu_custom_call")
    sm.check(all(exact.values()), f"fused train: counts not exact {exact}")
    return models


class _OfflineService(SLDAPredictionService):
    """The plan layer through a fresh jit per micro-batch — the offline
    reference the service's cached dispatch must reproduce."""

    def _dispatch_fn(self, plan_key):
        rule = self.svc.combine

        def run(keys, models, plan, chain_weights):
            zb = plan.predict_zbar(keys, models)
            yhat = jax.vmap(lambda z, e: z @ e)(zb, models.eta)
            return zb, yhat, _combine_yhat(rule, yhat, chain_weights,
                                           models.train_mse)
        return jax.jit(run)


def serving_phase(sm: Smoke, cfg, models, test, n_docs: int, batch: int,
                  expect: str):
    lens = np.asarray(test.mask.sum(-1)).astype(int)
    toks = np.asarray(test.tokens)
    docs = [toks[d, :lens[d]] for d in range(n_docs)]
    svc_cfg = ServiceConfig.calibrated(lens, max_doc_len=test.max_len,
                                       batch_docs=batch)
    key = jax.random.PRNGKey(9)
    svc = SLDAPredictionService(models, cfg, svc_cfg, key=key)
    off = _OfflineService(models, cfg, svc_cfg, key=key)
    sm.check(svc.backend == expect, f"serving: backend {svc.backend}")
    t0 = time.perf_counter()
    rids = [svc.submit(d) for d in docs[:batch]]     # first batch traces
    warm = svc.stats()["traces"]
    t1 = time.perf_counter()
    rids += [svc.submit(d) for d in docs[batch:]]
    svc.drain()
    t2 = time.perf_counter()
    oids = [off.submit(d) for d in docs]
    off.drain()
    res = [svc.result(r) for r in rids]
    ref = [off.result(r) for r in oids]
    diff = max(abs(a.yhat - b.yhat) for a, b in zip(res, ref))
    statuses = sorted({r.status for r in res})
    retraces = svc.stats()["traces"] - warm
    emit("serving", requests=len(res), statuses=statuses,
         first_batch_s=t1 - t0, steady_s=t2 - t1,
         steady_state_retraces=retraces,
         max_abs_diff_vs_offline=diff, backend=svc.backend)
    sm.check(statuses == [STATUS_OK], f"serving: statuses {statuses}")
    sm.check(retraces == 0, f"serving: {retraces} steady-state retraces")
    sm.check(all(np.isfinite(r.yhat) for r in res), "serving: non-finite ŷ")
    sm.check(diff == 0.0, f"serving: ŷ differs from offline by {diff}")


def one_chip(sm: Smoke, seed: int, sizes: dict, expect: str):
    cfg = dataclasses.replace(SLDA_MDNA, use_pallas=True)
    m = sizes["n_chains"]
    train, test = make_data(seed, cfg, sizes)
    emit("data", n_docs=sizes["n_docs"], n_train=train.n_docs,
         n_test=test.n_docs, vocab=cfg.vocab_size, topics=cfg.n_topics,
         max_len=train.max_len, chains=m,
         real_token_frac=float(jnp.mean(train.mask)))
    mse_p = weighted_phase(sm, cfg, train, test, m, expect)
    mse_j = weighted_phase(sm, dataclasses.replace(cfg, use_pallas=False),
                           train, test, m, "jnp")
    rel = abs(mse_p - mse_j) / mse_j
    emit("route_mse", pallas=mse_p, jnp=mse_j, rel_diff=rel)
    sm.check(rel <= 0.10, f"test MSE of the routes differ by {rel:.2%}")
    shards = partition(train, m)
    agreement_phase(cfg, shards, m, (expect, "jnp"))
    models = fused_train_phase(sm, cfg, shards, m, expect)
    serving_phase(sm, cfg, models, test, sizes["serve_docs"],
                  sizes["serve_batch"], expect)


def four_chips(sm: Smoke, seed: int, sizes: dict, devices):
    """parallel_slda_shard_map's Weighted Average, M chains over 4 devices
    vs 1 device, read through its `return_chains` seam."""
    from bench.checks import count_gap, word_counts
    cfg = SLDA_MDNA
    m = sizes["n_chains"]
    train, test = make_data(seed, cfg, sizes)
    wc = word_counts(train.tokens, train.mask, m, cfg.vocab_size)
    mesh4 = Mesh(np.array(devices[:4]), ("data",))
    mesh1 = Mesh(np.array(devices[:1]), ("data",))
    key = jax.random.PRNGKey(5)
    out = {}
    for name, mesh in (("4dev", mesh4), ("1dev", mesh1)):
        n_dev = mesh.devices.size
        cpd = m // n_dev
        # every device holds the whole training and test sets: its chains
        # train on their own shards and predict test ‖ train
        tr = jax.device_put(train, NamedSharding(mesh, P()))
        te = jax.device_put(test, NamedSharding(mesh, P()))
        run = functools.partial(parallel_slda_shard_map, cfg=cfg, mesh=mesh,
                                rule="weighted", chains_per_device=cpd,
                                return_chains=True)
        fit, c_s, r_s, hlo = compile_and_run(run, key, tr, te)
        held = sorted((s.device.id, s.data.shape[0])
                      for s in fit.models.phi.addressable_shards)
        per_dev_shape = f"s32[{cpd},{train.n_docs // m},{train.max_len}]"
        kinds = collective_kinds(hlo)
        gap = count_gap(fit.models.phi, wc, cfg.beta)
        mse = float(jnp.mean((fit.yhat - test.y) ** 2))
        emit(f"shard_map_{name}", devices=n_dev, chains_per_device=cpd,
             compile_s=c_s, run_s=r_s, test_mse=mse,
             r2=1.0 - mse / float(jnp.var(test.y)), collectives=kinds,
             chains_held=held, count_gap=gap,
             yhat_chains_shape=list(fit.yhat_chains.shape),
             per_device_shard_in_hlo=per_dev_shape in hlo,
             tpu_custom_call="tpu_custom_call" in hlo)
        sm.check("tpu_custom_call" in hlo, f"{name}: no tpu_custom_call")
        sm.check(per_dev_shape in hlo,
                 f"{name}: per-device chain shard {per_dev_shape} not in HLO")
        sm.check(len({d for d, _ in held}) == n_dev
                 and all(r == cpd for _, r in held),
                 f"{name}: chains not spread {held}")
        sm.check(gap <= 3e-3, f"{name}: chain φ̂ counts off their shards "
                 f"by {gap}")
        sm.check(fit.yhat_chains.shape == (m, test.n_docs + train.n_docs),
                 f"{name}: per-chain ŷ {fit.yhat_chains.shape}")
        if n_dev > 1:
            sm.check(kinds == {"all-gather": 1},
                     f"{name}: collectives {kinds}, not the one all_gather")
        out[name] = np.asarray(fit.yhat)
    diff = float(np.max(np.abs(out["4dev"] - out["1dev"])))
    equal = bool(np.array_equal(out["4dev"], out["1dev"]))
    emit("shard_map_layouts", equal=equal, max_abs_diff=diff)
    sm.check(equal, f"4-device ŷ != 1-device ŷ (max diff {diff})")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "devices", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    emit("device", platform=dev.platform, kind=dev.device_kind,
         count=len(devices), jax=jax.__version__, compile_cache=cache)

    sm = Smoke()
    t0 = time.perf_counter()
    sizes = dict(MDNA, serve_docs=96, serve_batch=32)
    if args.chips == 4:
        four_chips(sm, args.seed, sizes, devices)
    else:
        one_chip(sm, args.seed, sizes, expect="pallas")
    emit("total", seconds=time.perf_counter() - t0, failed=sm.failed)
    if sm.failed:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
