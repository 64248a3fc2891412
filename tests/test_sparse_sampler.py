"""The sparse two-stage sampler (DESIGN.md §Sparse-sampler), contract by
contract:

  * COLLAPSE — with the identity index (cap = T, everything occupied) the
    two-stage draw is BITWISE the dense inverse-CDF draw under shared
    uniforms: the stages degenerate (empty residual, stage 2 never
    fires), so the decomposition provably changes nothing at the point
    where the two samplers coincide.
  * DISTRIBUTIONAL EXACTNESS — for ANY index content (including caps far
    below the true occupancy, forcing the stage-2 residual correction),
    the measure of uniforms mapped to each topic equals the dense
    sampler's, asserted deterministically on a fine u-grid (the preimage
    of a topic is at most two intervals, so the grid bound is sharp).
  * CROSS-BACKEND BITWISE — pallas-interpret kernel ≡ blocked-jnp twin ≡
    ref oracle in sparse mode for the train, predict, and single-sweep
    entry points (the same three-way pin dense mode has).
  * DISPATCH MATRIX — plan-routed sparse cells over (layout × M ×
    spl): jnp and pallas-interpret agree bitwise per cell, counts stay
    exactly consistent with z, and the model still learns.  Sparse is
    its OWN sampler family (not bit-equal to dense; the Geweke tier in
    test_statistical.py pins its distribution to the model).
  * SERVING — switching `sampler_mode` on a live service allocates a
    DISTINCT jitted callable (the cfg is inside ExecutionPlan.cache_key)
    and `stats()` reports the active mode.
  * a hypothesis property over occupancy distributions × M ∈ {1, 4}.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (SLDAConfig, bucket_corpus, build_plan,
                        counts_from_assignments, partition,
                        topic_occupancy_index)
from repro.core.parallel import train_chains_keyed
from repro.data import make_slda_corpus, train_test_split
from repro.kernels import ops, ref
from repro.kernels.slda_predict import predict_uniforms
from repro.kernels.slda_train import train_uniforms
from repro.kernels.sparse import sparse_two_stage_draw
from repro.mathutil import upper_tri_ones


def _dense_draw(p, u):
    c = jnp.dot(p, upper_tri_ones(p.shape[-1]))
    return jnp.sum((c < (u * c[..., -1])[..., None]).astype(jnp.int32),
                   axis=-1)


# ----------------------------------------------------------- collapse

@pytest.mark.parametrize("t_dim", [3, 8, 17, 32])
def test_collapse_identity_index_bitwise_equals_dense(t_dim):
    """cap = T, identity index, everything occupied: every uniform maps
    to the SAME topic as the dense draw, bit for bit (the oracle
    contract the refactor rests on)."""
    B = 257
    p = jax.random.uniform(jax.random.PRNGKey(t_dim), (B, t_dim)) ** 3
    u = jax.random.uniform(jax.random.PRNGKey(t_dim + 100), (B,))
    idx = jnp.broadcast_to(jnp.arange(t_dim, dtype=jnp.int32), (B, t_dim))
    ones = jnp.ones((B, t_dim), jnp.float32)
    z_sp = sparse_two_stage_draw(p, u, idx, ones, ones)
    assert np.array_equal(np.asarray(z_sp), np.asarray(_dense_draw(p, u)))


# ----------------------------------------- deterministic distributional

@pytest.mark.parametrize("cap", [1, 2, 4])
def test_two_stage_distributionally_exact_any_index(cap):
    """Fine u-grid measure per topic == the dense sampler's, for random
    count tables indexed at caps BELOW the true occupancy (stage 2 must
    fire).  Each topic's preimage is ≤ 2 intervals under the two-stage
    map and 1 under dense, so |measure difference| ≤ 4/n_grid exactly —
    a deterministic statement of distributional equality, no Monte
    Carlo slack."""
    T, W, n = 11, 5, 40_000
    table = (jax.random.uniform(jax.random.PRNGKey(3), (W, T)) > 0.5) \
        .astype(jnp.float32) * 7.0
    idx, vm, om = topic_occupancy_index(table, cap)
    pw = jax.random.uniform(jax.random.PRNGKey(4), (W, T)) ** 2 + 1e-4
    us = (jnp.arange(n, dtype=jnp.float32) + 0.5) / n
    for w in range(W):
        p = jnp.broadcast_to(pw[w], (n, T))
        z = sparse_two_stage_draw(
            p, us, jnp.broadcast_to(idx[w], (n, cap)),
            jnp.broadcast_to(vm[w], (n, cap)),
            jnp.broadcast_to(om[w], (n, T)))
        frac = np.asarray(jnp.bincount(z, length=T)) / n
        ref_frac = np.asarray(pw[w] / pw[w].sum())
        np.testing.assert_allclose(frac, ref_frac, atol=4.0 / n,
                                   err_msg=f"word {w} cap {cap}")


# ------------------------------------------------ cross-backend bitwise

_T, _W, _DL = 8, 40, 9
_corpus_small, _ = make_slda_corpus(jax.random.PRNGKey(7), 12, _W, _T, _DL)


def _small_state(key):
    tokens, mask = _corpus_small.tokens, _corpus_small.mask
    k1, k2 = jax.random.split(key)
    z0 = jax.random.randint(k1, tokens.shape, 0, _T, jnp.int32)
    ndt0, ntw, nt = counts_from_assignments(tokens, mask, z0, _T, _W)
    seeds = jax.random.randint(k2, (tokens.shape[0],), 0, 2 ** 31 - 1,
                               jnp.int32)
    inv_len = 1.0 / jnp.maximum(mask.sum(-1), 1.0)
    return z0, ndt0, ntw, nt, seeds, inv_len


@pytest.mark.parametrize("cap", [2, 4])
def test_train_sparse_kernel_twin_oracle_bitwise(cap):
    tokens, mask, y = (_corpus_small.tokens, _corpus_small.mask,
                       _corpus_small.y)
    z0, ndt0, ntw, nt, seeds, inv_len = _small_state(jax.random.PRNGKey(1))
    eta = jnp.linspace(-1, 1, _T)
    kw = dict(alpha=0.1, beta=0.01, rho=0.5, n_sweeps=3, supervised=True,
              doc_block=4, sampler_mode="sparse", sparse_topic_cap=cap)
    zj, nj = ops.slda_train_sweeps(tokens, mask, z0, ndt0, y, inv_len,
                                   ntw, nt, eta, seeds, use_pallas=False,
                                   **kw)
    zp, np_ = ops.slda_train_sweeps(tokens, mask, z0, ndt0, y, inv_len,
                                    ntw, nt, eta, seeds, use_pallas=True,
                                    **kw)
    us = train_uniforms(seeds, 3, tokens.shape[1])
    zo, no = ref.ref_slda_train_sweeps(
        tokens, mask, us, z0, ndt0, y, inv_len, jnp.swapaxes(ntw, -1, -2),
        nt, eta, 0.1, 0.01, 0.5, True, 4, sampler_mode="sparse",
        sparse_topic_cap=cap)
    for a, b, tag in ((zj, zp, "twin/kernel z"), (zj, zo, "twin/oracle z"),
                      (nj, np_, "twin/kernel ndt"),
                      (nj, no, "twin/oracle ndt")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0,
                                   err_msg=f"cap={cap} {tag}")
    # sparse is its own family: must DIFFER from dense somewhere
    zd, _ = ops.slda_train_sweeps(tokens, mask, z0, ndt0, y, inv_len, ntw,
                                  nt, eta, seeds, use_pallas=False,
                                  **dict(kw, sampler_mode="dense"))
    assert np.any(np.asarray(zd) != np.asarray(zj))


def test_predict_and_single_sweep_sparse_bitwise():
    tokens, mask, y = (_corpus_small.tokens, _corpus_small.mask,
                       _corpus_small.y)
    z0, ndt0, ntw, nt, seeds, inv_len = _small_state(jax.random.PRNGKey(2))
    phi = jax.random.dirichlet(jax.random.PRNGKey(9),
                               jnp.full((_W,), 0.1), (_T,))
    pkw = dict(alpha=0.1, n_burnin=1, n_samples=2, doc_block=4,
               sampler_mode="sparse", sparse_topic_cap=3)
    aj, zj = ops.slda_predict_sweeps(tokens, mask, z0, ndt0, phi, seeds,
                                     use_pallas=False, **pkw)
    ap, zp = ops.slda_predict_sweeps(tokens, mask, z0, ndt0, phi, seeds,
                                     use_pallas=True, **pkw)
    up = predict_uniforms(seeds, 3, tokens.shape[1])
    ao, zo = ref.ref_slda_predict_sweeps(
        tokens, mask, up, z0, ndt0, jnp.swapaxes(phi, -1, -2), 0.1, 1,
        sampler_mode="sparse", sparse_topic_cap=3)
    for a, b in ((aj, ap), (aj, ao), (zj, zp), (zj, zo)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0)

    eta = jnp.linspace(-1, 1, _T)
    uni = jax.random.uniform(jax.random.PRNGKey(11), tokens.shape)
    skw = dict(alpha=0.1, beta=0.01, rho=0.5, sampler_mode="sparse",
               sparse_topic_cap=3)
    gj = ops.slda_gibbs_sweep(tokens, mask, uni, z0, ndt0, y, inv_len,
                              ntw, nt, eta, use_pallas=False, **skw)
    gp = ops.slda_gibbs_sweep(tokens, mask, uni, z0, ndt0, y, inv_len,
                              ntw, nt, eta, use_pallas=True, doc_block=4,
                              **skw)
    for a, b in zip(gj, gp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0)


# ------------------------------------------------------ dispatch matrix

_CFG = SLDAConfig(n_topics=4, vocab_size=24, n_iters=5, rho=0.25,
                  n_pred_burnin=1, n_pred_samples=2, count_rebuild_every=2,
                  sampler_mode="sparse", sparse_topic_cap=2)
_D_TOTAL, _MAXLEN = 32, 12
_corp, _ = make_slda_corpus(jax.random.PRNGKey(0), _D_TOTAL + 16, 24, 4,
                            _MAXLEN, rho=0.25, doc_len_dist="lognormal")
_train, _test = train_test_split(_corp, _D_TOTAL)


def _sp_cfg(backend, spl, layout):
    return dataclasses.replace(
        _CFG, use_pallas=(backend == "pallas-interpret"),
        sweeps_per_launch=spl, n_iters=_CFG.n_iters if spl == 1 else 9,
        length_buckets=3 if layout == "bucketed" else 0,
        bucket_overhead_docs=0.0)


def _sched(layout, shards):
    return bucket_corpus(shards, 3, overhead_docs=0) \
        if layout == "bucketed" else shards


@pytest.mark.parametrize("spl", [1, 4])
@pytest.mark.parametrize("m", [1, 4])
@pytest.mark.parametrize("layout", ["padded", "bucketed"])
def test_dispatch_matrix_sparse_train(layout, m, spl):
    """Sparse plan cells (cap=2 < T=4 keeps stage 2 live), holding the
    SAME contract as the dense dispatch matrix: spl=1 cells bitwise-agree
    across backends; spl>1 cells are each their own exact member of the
    fused-sampler family (the stair executor's whole-corpus in-launch
    refresh vs the blocks executor's per-bucket refresh — not bitwise
    comparable, dense or sparse), so both backends are instead held to
    exact count consistency and the learnability guard.  Covers the
    blocks AND stair executors (bucketed/jnp/spl>1)."""
    shards = partition(_train, m)
    keys = jax.random.split(jax.random.PRNGKey(1), m)
    out = {}
    for backend in ("jnp", "pallas-interpret"):
        cfg = _sp_cfg(backend, spl, layout)
        out[backend] = jax.jit(train_chains_keyed, static_argnums=(2,))(
            keys, _sched(layout, shards), cfg)
    (state, model), (state_p, model_p) = (out["jnp"],
                                          out["pallas-interpret"])
    if spl == 1:
        for f in ("z", "ndt", "ntw", "nt", "eta"):
            np.testing.assert_allclose(
                np.asarray(getattr(state, f)),
                np.asarray(getattr(state_p, f)),
                atol=0, err_msg=f"{layout}/{m}/spl{spl} state.{f}")
    for st, mdl in ((state, model), (state_p, model_p)):
        nd, nw, nt = jax.vmap(
            lambda t, mm, z: counts_from_assignments(t, mm, z, 4, 24))(
            shards.tokens, shards.mask, st.z)
        np.testing.assert_allclose(np.asarray(nd), np.asarray(st.ndt),
                                   atol=0)
        np.testing.assert_allclose(np.asarray(nw), np.asarray(st.ntw),
                                   atol=0)
        np.testing.assert_allclose(np.asarray(nt), np.asarray(st.nt),
                                   atol=0)
        assert float(jnp.mean(mdl.train_mse)) < \
            0.6 * float(jnp.var(shards.y))


# -------------------------------------------------------------- serving

def test_service_mode_switch_allocates_distinct_callable():
    """`set_sampler_mode` flips the cfg inside every future plan cache
    key: the next flush compiles a NEW jitted callable (count grows),
    switching back reuses the old one (count stays), and `stats()`
    reports the active mode + plan-cache key count."""
    from repro.core import train_chains
    from repro.serving import ServiceConfig, SLDAPredictionService

    cfg = SLDAConfig(n_topics=8, vocab_size=64, n_iters=3,
                     n_pred_burnin=1, n_pred_samples=2)
    corp, _ = make_slda_corpus(jax.random.PRNGKey(0), 48, 64, 8, 32,
                               doc_len_dist="lognormal")
    models = train_chains(jax.random.PRNGKey(1), partition(corp, 2), cfg)
    lens = np.asarray(corp.mask.sum(-1)).astype(int)
    svc_cfg = ServiceConfig.calibrated(lens, max_doc_len=32, batch_docs=8,
                                       n_buckets=2)
    svc = SLDAPredictionService(models, cfg, svc_cfg,
                                key=jax.random.PRNGKey(9))
    toks = np.asarray(corp.tokens)
    docs = [toks[d, :max(int(lens[d]), 1)] for d in range(16)]

    for d in docs[:8]:
        svc.submit(d)
    st = svc.stats()
    assert st["sampler_mode"] == "dense"
    assert st["compiled_plans"] == 1

    svc.set_sampler_mode("sparse")
    for d in docs[8:16]:
        svc.submit(d)
    svc.drain()
    st = svc.stats()
    assert st["sampler_mode"] == "sparse"
    assert st["compiled_plans"] == 2        # distinct jitted callable

    svc.set_sampler_mode("dense")            # switching back is free
    for d in docs[:8]:
        svc.submit(d)
    svc.drain()
    st = svc.stats()
    assert st["sampler_mode"] == "dense"
    assert st["compiled_plans"] == 2
    with pytest.raises(ValueError):
        svc.set_sampler_mode("dense-ish")


# -------------------------------------------------- hypothesis property

try:  # the rest of this module must still run without hypothesis
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    _HAVE_HYPOTHESIS = False
    given = settings = lambda *a, **k: (lambda f: f)

    class st:  # noqa: N801 — placeholder so the decorators below parse
        sampled_from = integers = floats = data = staticmethod(
            lambda *a, **k: None)


@pytest.mark.skipif(not _HAVE_HYPOTHESIS, reason=(
    "property tests need hypothesis (pip install -r requirements-dev.txt)"))
@settings(max_examples=15, deadline=None)
@given(
    m=st.sampled_from([1, 4]),
    cap=st.integers(1, 6),
    conc=st.floats(0.05, 4.0),
    data=st.data(),
)
def test_sparse_property_occupancy_and_chain_batching(m, cap, conc, data):
    """For every occupancy regime (peaked to flat φ via the corpus
    concentration knob), every cap (1 to > T), and M ∈ {1, 4}: the
    chain-batched sparse train equals the vmapped single-chain sparse
    train bitwise, padded tokens never move, and ndt stays exactly
    consistent with z."""
    seed = data.draw(st.integers(0, 2 ** 16))
    n_topics, vocab, n_docs, doc_len = 5, 24, 6, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    corp, _ = make_slda_corpus(ks[0], m * n_docs, vocab, n_topics, doc_len,
                               phi_concentration=conc)
    tokens = corp.tokens.reshape(m, n_docs, doc_len)
    mask = corp.mask.reshape(m, n_docs, doc_len)
    y = corp.y.reshape(m, n_docs)
    z0 = jax.random.randint(ks[1], (m, n_docs, doc_len), 0, n_topics,
                            jnp.int32)
    d_idx = jnp.arange(n_docs)[:, None]
    ndt0 = jax.vmap(lambda z, mm: jnp.zeros((n_docs, n_topics))
                    .at[d_idx, z].add(mm))(z0, mask)
    ntw = jax.vmap(lambda z, t, mm: jnp.zeros((n_topics, vocab))
                   .at[z, t].add(mm))(z0, tokens, mask)
    nt = ntw.sum(-1)
    inv_len = 1.0 / jnp.maximum(mask.sum(-1), 1.0)
    eta = jax.random.normal(ks[3], (m, n_topics))
    seeds = jax.random.randint(ks[4], (m, n_docs), 0, 2 ** 31 - 1,
                               jnp.int32)
    kw = dict(alpha=0.1, beta=0.01, rho=0.5, n_sweeps=2, doc_block=4,
              use_pallas=False, sampler_mode="sparse",
              sparse_topic_cap=cap)
    z_v, ndt_v = jax.vmap(functools.partial(ops.slda_train_sweeps, **kw))(
        tokens, mask, z0, ndt0, y, inv_len, ntw, nt, eta, seeds)
    z_c, ndt_c = ops.slda_train_sweeps(
        tokens, mask, z0, ndt0, y, inv_len, ntw, nt, eta, seeds,
        chain_axis=True, **kw)
    assert np.array_equal(np.asarray(z_v), np.asarray(z_c))
    np.testing.assert_allclose(np.asarray(ndt_v), np.asarray(ndt_c),
                               atol=0)
    pad = np.asarray(mask) == 0
    assert np.array_equal(np.asarray(z_c)[pad], np.asarray(z0)[pad])
    ndt_r = jax.vmap(lambda z, mm: jnp.zeros((n_docs, n_topics))
                     .at[d_idx, z].add(mm))(z_c, mask)
    np.testing.assert_allclose(np.asarray(ndt_c), np.asarray(ndt_r),
                               atol=0)


@pytest.mark.parametrize("backend", ["jnp", "pallas-interpret", "pallas"])
def test_sparse_plan_refused_on_compiled_route(backend):
    """The sparse draw has no compiled TPU kernel: a sparse plan on the
    compiled pallas route fails at plan time (never runs interpreted);
    the jnp and interpret routes keep it."""
    shards = partition(_train, 2)
    if backend == "pallas":
        with pytest.raises(NotImplementedError, match="sparse"):
            build_plan(shards, _CFG, backend)
    else:
        assert build_plan(shards, _CFG, backend).backend == backend
