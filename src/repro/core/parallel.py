"""The four algorithms of Section IV, sharing ONE plan-driven sampler.

  non-parallel      one chain on the full training corpus (paper benchmark 1)
  naive             M chains; pool the *sampled topics* as if drawn on the
                    full corpus, fit (η, φ) globally, predict once
                    (paper benchmark 2 — exhibits quasi-ergodicity)
  simple-average    M chains; each predicts the test set; Eq. (7) combine
  weighted-average  M chains; each predicts test AND full train set (for the
                    weights); Eq. (8)-(9) combine

Every entry point here is a thin wrapper over the unified execution
plan (`core.plan`, DESIGN.md §Execution-plan): `build_schedule` decides
the data layout (padded = the degenerate 1-bucket schedule; length
bucketing when `cfg.length_buckets > 0` — built host-side, outside
jit), and `ExecutionPlan` owns the routing (executor, chain batching,
sweeps-per-launch schedule, refresh cadence).  The EM loop exists
exactly once, in `plan.py`; there are no per-layout twins left.

At `sweeps_per_launch=1` the chain-batched loop reproduces the seed
semantics BIT-FOR-BIT for every (layout × backend × M) cell
(tests/test_dispatch_matrix.py); at `>1` it is the fused multi-sweep
sampler family of DESIGN.md §Train-kernel.

The multi-device form — `shard_map` over the mesh's chain axis with
zero collectives until the final prediction gather, and
`chains_per_device` local chains per mesh slice riding these same
entry points (one plan built per shard) — lives in
`repro.launch.slda_parallel`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from . import combine
from .gibbs import train_chain
from .plan import build_plan, build_schedule
from .predict import predict
from .regression import solve_eta_ols
from .types import (BucketedCorpus, Corpus, SLDAConfig, SLDAModel,
                    _concat_corpora, partition)


# ----------------------------------------------- chain-batched training

def train_chains_keyed(keys: jax.Array, shards, cfg: SLDAConfig):
    """Train M independent chains (no communication) from explicit
    per-chain keys [M] — the entry the multi-device runner uses with
    fold_in-derived keys.  shards is [M, D/M, ...] — a Corpus, or a
    BucketedCorpus built from one (`build_schedule(partition(...))`)
    for the ragged execution layer.  Returns (GibbsState, SLDAModel),
    each with leading chain dim."""
    return build_plan(shards, cfg).train(keys)


def train_chains(key: jax.Array, shards, cfg: SLDAConfig):
    """Train M independent chains (no communication). shards is [M, D/M, ...]."""
    m = (shards.n_chains if isinstance(shards, BucketedCorpus)
         else shards.tokens.shape[0])
    _, models = train_chains_keyed(jax.random.split(key, m), shards, cfg)
    return models  # SLDAModel with leading chain dim [M, ...]


# --------------------------------------------- chain-batched prediction

def predict_chains_keyed(keys: jax.Array, models: SLDAModel, corpus,
                         cfg: SLDAConfig) -> jnp.ndarray:
    """Every chain predicts every document of `corpus` → [M, D], from
    explicit per-chain keys [M].  The corpus is SHARED across chains
    (one token tile per doc block on the kernel path, one folded
    row-op on the jnp path); a `BucketedCorpus` routes through the
    ragged execution layer."""
    return build_plan(corpus, cfg).predict(keys, models)


def predict_chains(key: jax.Array, models: SLDAModel, corpus,
                   cfg: SLDAConfig) -> jnp.ndarray:
    """Every chain predicts every document of `corpus` → [M, D]."""
    m = models.eta.shape[0]
    return predict_chains_keyed(jax.random.split(key, m), models, corpus,
                                cfg)


# ---------------------------------------------------------------- algorithms
# Host-side orchestrators: schedules are built from CONCRETE corpora
# when cfg.length_buckets > 0 (shapes are data-dependent — call the
# orchestrators OUTSIDE jit then), while the padded degenerate schedule
# is shape-only, so with length_buckets == 0 each orchestrator stays
# fully jit-able.  The chain phases run through these module-level jits
# either way; at sweeps_per_launch=1 the bucketed run is bit-identical
# to the padded one (tests/test_dispatch_matrix.py) and the speedup
# comes from sweep compute scaling with Σ true tokens
# (BENCH_slda_ragged.json).

_train_chain_jit = jax.jit(train_chain, static_argnums=(2,))
_train_chains_jit = jax.jit(train_chains, static_argnums=(2,))
_train_chains_keyed_jit = jax.jit(train_chains_keyed, static_argnums=(2,))
_predict_chains_jit = jax.jit(predict_chains, static_argnums=(3,))
_predict_jit = jax.jit(predict, static_argnums=(3,))


def run_nonparallel(key, train: Corpus, test: Corpus, cfg: SLDAConfig):
    k1, k2 = jax.random.split(key)
    _, model = _train_chain_jit(k1, build_schedule(train, cfg), cfg)
    return _predict_jit(k2, model, build_schedule(test, cfg), cfg)


def run_naive(key, train: Corpus, test: Corpus, cfg: SLDAConfig, m: int):
    """Naive Combination: pool sub-sampled topics, then fit + predict once."""
    k1, k2, k3 = jax.random.split(key, 3)
    shards = build_schedule(partition(train, m), cfg)
    keys = jax.random.split(k1, m)
    states, _ = _train_chains_keyed_jit(keys, shards, cfg)

    # step 3: treat the union of sub-samples as one global sample
    lengths = jnp.maximum(shards.lengths(), 1.0)             # [M, D/M]
    zbar_all = (states.ndt / lengths[..., None]).reshape(-1, cfg.n_topics)
    eta = solve_eta_ols(zbar_all, shards.y.reshape(-1))      # 3(a): OLS
    ntw = states.ntw.sum(0)                                  # 3(b): pooled φ
    phi = (ntw + cfg.beta) / (ntw.sum(-1, keepdims=True) + cfg.vocab_size * cfg.beta)
    model = SLDAModel(phi=phi, eta=eta,
                      train_mse=jnp.zeros(()), train_acc=jnp.zeros(()))
    return _predict_jit(k3, model, build_schedule(test, cfg), cfg)


def run_simple_average(key, train: Corpus, test: Corpus, cfg: SLDAConfig,
                       m: int, alive=None):
    k1, k2 = jax.random.split(key)
    models = _train_chains_jit(k1, build_schedule(partition(train, m), cfg),
                               cfg)
    yhat = _predict_chains_jit(k2, models, build_schedule(test, cfg), cfg)
    return combine.simple_average(yhat, alive=alive)


def _combine_weighted(yhat_te, yhat_tr, train_y, cfg: SLDAConfig, alive):
    """Eq. (8)-(9): weight each chain's test predictions by its
    full-training-set accuracy (binary) or MSE (continuous) — the ONE
    copy of the weighting rule."""
    if cfg.label_type == "binary":
        acc = ((yhat_tr > 0.5) == (train_y[None, :] > 0.5)).mean(-1)
        return combine.weighted_average(yhat_te, train_acc=acc, alive=alive)
    mse = ((yhat_tr - train_y[None, :]) ** 2).mean(-1)
    return combine.weighted_average(yhat_te, train_mse=mse, alive=alive)


def run_weighted_average(key, train: Corpus, test: Corpus, cfg: SLDAConfig,
                         m: int, alive=None):
    """The weights use the *full training set* MSE/accuracy of each local
    model (Section III-C(d)) — this extra full-train prediction pass is why
    the paper reports Weighted Average as the slowest algorithm.  With
    `cfg.fuse_weighted_predict` (the default) the test and train passes
    run as ONE chain-batched fused pass over the concatenated corpus —
    same sweeps per document, half the sequential token-loop launches.

    Host spans `slda.fit.schedule`, `slda.fit.train`, `slda.fit.predict`
    and `slda.fit.combine` name each phase in a profiler trace."""
    k1, k2, k3 = jax.random.split(key, 3)
    with TraceAnnotation("slda.fit.schedule"):
        shards = build_schedule(partition(train, m), cfg)
        if cfg.fuse_weighted_predict:
            both = build_schedule(_concat_corpora(test, train), cfg)
        else:
            test_s = build_schedule(test, cfg)
            train_s = build_schedule(train, cfg)
    with TraceAnnotation("slda.fit.train"):
        models = _train_chains_jit(k1, shards, cfg)
    with TraceAnnotation("slda.fit.predict"):
        if cfg.fuse_weighted_predict:
            yhat = _predict_chains_jit(k2, models, both, cfg)
            yhat_te, yhat_tr = yhat[:, :test.n_docs], yhat[:, test.n_docs:]
        else:
            yhat_te = _predict_chains_jit(k2, models, test_s, cfg)
            yhat_tr = _predict_chains_jit(k3, models, train_s, cfg)
    with TraceAnnotation("slda.fit.combine"):
        return _combine_weighted(yhat_te, yhat_tr, train.y, cfg, alive)


ALGORITHMS = {
    "nonparallel": run_nonparallel,
    "naive": run_naive,
    "simple": run_simple_average,
    "weighted": run_weighted_average,
}
