"""The execution-plan dispatch matrix, cell by cell (DESIGN.md
§Execution-plan).

One parametrized sweep over (padded | bucketed) × (M ∈ {1, 4}) ×
(jnp | pallas-interpret) × (spl ∈ {1, 4}) asserting the documented
contract per cell:

  * spl=1 — BIT-IDENTITY: every cell reproduces the seed-semantics
    reference (per-sweep threefry uniforms, η solve every sweep,
    globally sweep-frozen counts) built here from the core primitives
    (`init_state`/`sweep`/`solve_eta` — the vmapped per-document
    oracle, independent of the plan loop), per document, under any
    bucketing/permutation.  State AND model — ndt/η live in original
    doc order at every EM boundary, so even cross-document reductions
    agree.
  * spl=4 — STATISTICAL EQUIVALENCE: each cell is its own member of
    the fused sampler family (counter-hash PRNG, delayed counts).
    Asserted: counts exactly consistent with the final z (exactness of
    the EM boundary never depends on the cell), the remainder launch
    keeps total sweeps == n_iters (covered by n_iters % spl != 0), and
    the model lands in the reference's quality ballpark.

Prediction cells: every (layout × M × backend) combination must be
bit-identical to the reference single-model fused pass (prediction is
document-independent under frozen φ̂ — no spl axis).

This file replaces the ad-hoc core-level parity asserts previously
spread over test_chain_batched.py / test_ragged.py; the ops-level and
kernel-parity tests stay where they were.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (GibbsState, SLDAConfig, SLDAModel, bucket_corpus,
                        build_schedule, counts_from_assignments, init_state,
                        partition, phi_hat, solve_eta, sweep, zbar)
from repro.core.parallel import (predict_chains_keyed, run_weighted_average,
                                 train_chains_keyed)
from repro.data import make_slda_corpus, train_test_split

CFG = SLDAConfig(n_topics=4, vocab_size=24, n_iters=5, rho=0.25,
                 n_pred_burnin=1, n_pred_samples=2, count_rebuild_every=2)
D_TOTAL, MAX_LEN = 32, 12

_corpus, _ = make_slda_corpus(jax.random.PRNGKey(0), D_TOTAL + 16, 24, 4,
                              MAX_LEN, rho=0.25, doc_len_dist="lognormal")
_train, _test = train_test_split(_corpus, D_TOTAL)
_KEY = jax.random.PRNGKey(1)


def _cfg(backend, spl, layout):
    # spl>1 cells run 9 iters (2 full fused launches + a 1-sweep
    # remainder — the remainder path is part of the contract); the η
    # solve happens per LAUNCH there, so 5 iters would leave the fused
    # family visibly under-converged vs the per-sweep-solve reference
    return dataclasses.replace(
        CFG, use_pallas=(backend == "pallas-interpret"),
        sweeps_per_launch=spl, n_iters=CFG.n_iters if spl == 1 else 9,
        length_buckets=3 if layout == "bucketed" else 0,
        bucket_overhead_docs=0.0)


def _schedule_for(layout, shards, cfg):
    if layout == "bucketed":
        return bucket_corpus(shards, 3, overhead_docs=0)
    return shards


# ------------------------------------------------- seed-semantics reference

def _ref_chain(key, corpus, cfg):
    """The seed path, from primitives — a verbatim reconstruction of the
    pre-plan single-chain EM loop (one threefry sweep per η solve,
    count_rebuild_every cadence, the same lax.scan structure): what
    every spl=1 cell must hit bit-for-bit."""
    k_init, k_sweeps = jax.random.split(key)
    state0 = init_state(k_init, corpus, cfg)
    every = cfg.count_rebuild_every

    def em_step(state, inp):
        k, it = inp
        rebuild = (it % every == 0) if every > 0 else False
        state = sweep(k, corpus, state, cfg, supervised=True,
                      exact_rebuild=rebuild)
        eta = solve_eta(zbar(state, corpus), corpus.y, cfg)
        return GibbsState(state.z, state.ndt, state.ntw, state.nt,
                          eta), None

    state, _ = jax.lax.scan(
        em_step, state0, (jax.random.split(k_sweeps, cfg.n_iters),
                          jnp.arange(cfg.n_iters)))
    yhat = zbar(state, corpus) @ state.eta
    mse = jnp.mean((yhat - corpus.y) ** 2)
    acc = jnp.mean(((yhat > 0.5) == (corpus.y > 0.5)).astype(jnp.float32))
    model = SLDAModel(phi=phi_hat(state, cfg), eta=state.eta,
                      train_mse=mse, train_acc=acc)
    return state, model


@functools.lru_cache(maxsize=None)
def _reference(m):
    """Seed reference for M = m chains on the padded shards: the
    VMAPPED per-chain loop — the `jax.vmap(train_chain)` equivalence
    class every chain-batched path has been pinned to since the
    chain-batching PR (layout/backend/spl-independent by the dispatch
    contract)."""
    cfg = _cfg("jnp", 1, "padded")
    shards = partition(_train, m)
    keys = jax.random.split(_KEY, m)
    state, model = jax.jit(jax.vmap(_ref_chain, in_axes=(0, 0, None)),
                           static_argnums=(2,))(keys, shards, cfg)
    return jax.tree.map(np.asarray, (state, model))


def _ref_predict_one(key, phi, eta, cfg):
    """The pre-plan single-model fused prediction pass, verbatim —
    same key tree as predict_chains_keyed."""
    from repro.kernels import ops
    D = _test.n_docs
    k_init, k_seeds = jax.random.split(key)
    z0 = jax.random.randint(k_init, _test.tokens.shape, 0,
                            cfg.n_topics, jnp.int32)
    d_idx = jnp.arange(D)[:, None]
    ndt0 = jnp.zeros((D, cfg.n_topics), jnp.float32) \
        .at[d_idx, z0].add(_test.mask)
    seeds = jax.random.randint(k_seeds, (D,), 0,
                               jnp.iinfo(jnp.int32).max, jnp.int32)
    ndt_avg, _ = ops.slda_predict_sweeps(
        _test.tokens, _test.mask, z0, ndt0, phi, seeds, alpha=cfg.alpha,
        n_burnin=cfg.n_pred_burnin, n_samples=cfg.n_pred_samples,
        doc_block=cfg.pred_doc_block, use_pallas=False)
    zb = ndt_avg / jnp.maximum(_test.lengths(), 1.0)[:, None]
    return zb @ eta


@functools.lru_cache(maxsize=None)
def _ref_predictions(m):
    """Reference prediction: the vmapped pre-plan fused pass — the
    `jax.vmap(predict)` equivalence class.  Evaluated EAGERLY so the
    deterministic ŷ epilogue (division + Eq. (5) matmul) compiles as
    the same standalone batched ops as the plan cells' — whole-program
    jit would let XLA fuse the epilogue differently per producer, which
    costs a final-ulp on some documents without touching the
    per-document sampler bits."""
    _, model = _reference(m)
    keys = jax.random.split(jax.random.PRNGKey(2), m)
    cfg = _cfg("jnp", 1, "padded")
    out = jax.vmap(_ref_predict_one, in_axes=(0, 0, 0, None))(
        keys, jnp.asarray(model.phi), jnp.asarray(model.eta), cfg)
    return np.asarray(out)


# ------------------------------------------------------------ the matrix

@pytest.mark.parametrize("spl", [1, 4])
@pytest.mark.parametrize("backend", ["jnp", "pallas-interpret"])
@pytest.mark.parametrize("m", [1, 4])
@pytest.mark.parametrize("layout", ["padded", "bucketed"])
def test_dispatch_matrix_train(layout, m, backend, spl):
    cfg = _cfg(backend, spl, layout)
    shards = partition(_train, m)
    sched = _schedule_for(layout, shards, cfg)
    keys = jax.random.split(_KEY, m)
    state, model = jax.jit(train_chains_keyed, static_argnums=(2,))(
        keys, sched, cfg)
    ref_state, ref_model = _reference(m)

    if spl == 1:   # bit-identity cell
        for f in ("z", "ndt", "ntw", "nt", "eta"):
            np.testing.assert_allclose(
                np.asarray(getattr(state, f)), getattr(ref_state, f),
                atol=0, err_msg=f"{layout}/{m}/{backend}/spl1 state.{f}")
        for f in ("phi", "eta", "train_mse", "train_acc"):
            np.testing.assert_allclose(
                np.asarray(getattr(model, f)), getattr(ref_model, f),
                atol=0, err_msg=f"{layout}/{m}/{backend}/spl1 model.{f}")
        return

    # spl>1: own sampler family — exact count consistency with z (the
    # remainder launch is exercised: n_iters=9, spl=4), model learnable
    nd, nw, nt = jax.vmap(
        lambda t, mm, z: counts_from_assignments(
            t, mm, z, cfg.n_topics, cfg.vocab_size))(
        shards.tokens, shards.mask, state.z)
    np.testing.assert_allclose(np.asarray(nd), np.asarray(state.ndt),
                               atol=0)
    np.testing.assert_allclose(np.asarray(nw), np.asarray(state.ntw),
                               atol=0)
    np.testing.assert_allclose(np.asarray(nt), np.asarray(state.nt),
                               atol=0)
    # each spl>1 cell is a different (exact) member of the fused
    # family — pin quality to the label variance (the statistical
    # tier's Geweke test covers distribution-level correctness)
    assert float(jnp.mean(model.train_mse)) < \
        0.6 * float(jnp.var(shards.y))


@pytest.mark.parametrize("backend", ["jnp", "pallas-interpret"])
@pytest.mark.parametrize("m", [1, 4])
@pytest.mark.parametrize("layout", ["padded", "bucketed"])
def test_dispatch_matrix_predict(layout, m, backend):
    """Prediction cells: bit-identical to the reference fused pass for
    every layout × M × backend (no spl axis — prediction is
    document-independent under frozen φ̂)."""
    cfg = _cfg(backend, 1, layout)
    _, ref_model = _reference(m)
    models = jax.tree.map(jnp.asarray, ref_model)
    sched = (_test if layout == "padded"
             else bucket_corpus(_test, 3, overhead_docs=0))
    keys = jax.random.split(jax.random.PRNGKey(2), m)
    # eager like the reference — see _ref_predictions on why
    yhat = predict_chains_keyed(keys, models, sched, cfg)
    np.testing.assert_allclose(np.asarray(yhat), _ref_predictions(m),
                               atol=0,
                               err_msg=f"{layout}/{m}/{backend}")


def test_weighted_average_end_to_end_bitwise_padded_vs_bucketed():
    """The whole Weighted Average algorithm through the unified entry
    point: a length_buckets>0 config (host-side schedules) must equal
    the padded jit'd run bit-for-bit at spl=1 — the end-to-end
    inverse-permutation contract."""
    cfg_pad = _cfg("jnp", 1, "padded")
    cfg_bkt = _cfg("jnp", 1, "bucketed")
    key = jax.random.PRNGKey(3)
    # same phase-jit structure on both sides (the combine epilogue runs
    # eagerly either way) — only the schedule layout differs
    y_pad = run_weighted_average(key, _train, _test, cfg_pad, 4)
    y_bkt = run_weighted_average(key, _train, _test, cfg_bkt, 4)
    np.testing.assert_allclose(np.asarray(y_pad), np.asarray(y_bkt),
                               atol=0)


def test_pallas_route_off_tpu_is_interpreted():
    """Off a TPU, `use_pallas=True` resolves to the interpreted route
    and the kernel wrappers interpret: the compiled route is only ever
    taken where every device is a TPU."""
    from repro.kernels import ops
    assert jax.default_backend() != "tpu"
    cfg = dataclasses.replace(CFG, use_pallas=True)
    assert cfg.resolve_backend() == "pallas-interpret"
    assert cfg.resolve_backend(jax.devices()) == "pallas-interpret"
    assert ops._interpret()
    assert CFG.resolve_backend() == "jnp"
