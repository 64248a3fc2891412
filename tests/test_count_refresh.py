"""The count refresh at the EM boundary (`ExecutionPlan._refresh_and_solve`):
one exact recount of ntw from the new assignments, nt = ntw.sum(-1), ndt
from the sweep.  Counts are integers in float32 far below 2^24, so every
exact refresh gives the same tables, and a fit is bit-identical to the one
the earlier delta refresh (with its periodic full rebuild) produced.

The goldens in `count_refresh_goldens.npz` were recorded with the delta
refresh; `python tests/test_count_refresh.py` rewrites them from the code
it runs against.
"""
import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Corpus, SLDAConfig, build_schedule,
                        counts_from_assignments, partition)
from repro.core.parallel import predict_chains, train_chains_keyed

CFG = SLDAConfig(n_topics=4, vocab_size=24, n_iters=7, rho=0.25,
                 n_pred_burnin=2, n_pred_samples=2)
EXECUTORS = {"seed": (1, 0), "blocks": (2, 0), "stair": (2, 2)}
GOLDENS = pathlib.Path(__file__).with_name("count_refresh_goldens.npz")
M = 2


def _corpus(d=24, n=12, seed=0):
    rng = np.random.default_rng(seed)
    lens = rng.integers(2, n + 1, d)
    mask = (np.arange(n)[None, :] < lens[:, None]).astype(np.float32)
    return Corpus(tokens=jnp.asarray(rng.integers(0, CFG.vocab_size, (d, n)),
                                     jnp.int32),
                  mask=jnp.asarray(mask),
                  y=jnp.asarray(rng.normal(size=d), jnp.float32))


def _fit(name):
    """Train M chains on the executor `name`, predict the corpus → the
    arrays the goldens hold, plus the schedule the fit ran on."""
    spl, buckets = EXECUTORS[name]
    cfg = dataclasses.replace(CFG, sweeps_per_launch=spl,
                              length_buckets=buckets)
    corpus = _corpus()
    shards = build_schedule(partition(corpus, M), cfg)
    keys = jax.random.split(jax.random.PRNGKey(5), M)
    state, models = jax.jit(train_chains_keyed, static_argnums=(2,))(
        keys, shards, cfg)
    yhat = jax.jit(predict_chains, static_argnums=(3,))(
        jax.random.PRNGKey(6), models, build_schedule(corpus, cfg), cfg)
    out = dict(z=state.z, ndt=state.ndt, ntw=state.ntw, nt=state.nt,
               eta=state.eta, phi=models.phi, train_mse=models.train_mse,
               yhat=yhat)
    return {k: np.asarray(v) for k, v in out.items()}, partition(corpus, M)


@pytest.mark.parametrize("name", list(EXECUTORS))
def test_fit_matches_goldens_and_counts_are_a_recount(name):
    got, shards = _fit(name)
    want = np.load(GOLDENS)
    for k, v in got.items():
        np.testing.assert_array_equal(v, want[f"{name}.{k}"], err_msg=k)
    # the final tables are a fresh recount of the final z, exactly
    _, ntw, _ = jax.vmap(lambda t, m_, z: counts_from_assignments(
        t, m_, z, CFG.n_topics, CFG.vocab_size))(
        shards.tokens, shards.mask, jnp.asarray(got["z"]))
    np.testing.assert_array_equal(got["ntw"], np.asarray(ntw))
    np.testing.assert_array_equal(got["nt"], got["ntw"].sum(-1))


if __name__ == "__main__":
    np.savez_compressed(sys.argv[1] if len(sys.argv) > 1 else GOLDENS,
                        **{f"{name}.{k}": v for name in EXECUTORS
                           for k, v in _fit(name)[0].items()})
