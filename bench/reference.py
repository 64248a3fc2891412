"""The plain reference of sLDA's stochastic EM (McAuliffe & Blei 2008;
the communication-free parallel form of arXiv:1708.03052 §III).

Written from the papers, in plain `jax.numpy`, and independent of the
program under test: it imports nothing from it.  `dtype` is float32 for
the reference itself and bfloat16 for the precision control, which puts
this code in the program's place one precision lower.

Per chain, one EM iteration is a document-parallel collapsed Gibbs sweep
against the topic-word counts frozen at the start of the sweep (each
document walks its tokens in order and updates its own counts), a
rebuild of the counts from the new assignments, and the ridge solve of η
(Eq. 2).  Prediction samples the topics of new documents under the
frozen φ̂ (Eqs. 4-5) and averages z̄ over the sample sweeps after
burn-in.  Weighted Average combines the chains by their full-training-
set MSE (continuous labels) or accuracy (binary labels), Eqs. 8-9.
No matrix product is taken at the chip's default precision: sums of
products are elementwise, and the one Gram matrix is taken at HIGHEST.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.gen import Docs

HIGHEST = jax.lax.Precision.HIGHEST


class HP(NamedTuple):
    """The model's hyperparameters (hashable: a static jit argument)."""
    n_topics: int
    vocab_size: int
    alpha: float
    beta: float
    rho: float
    mu: float
    sigma: float
    n_iters: int
    n_pred_burnin: int
    n_pred_samples: int

    @classmethod
    def of(cls, conf: dict) -> "HP":
        return cls(*(conf[k] for k in cls._fields))


class Models(NamedTuple):
    """Per-chain exports, leading chain axis [M, ...]."""
    phi: jax.Array        # [M, T, W]
    eta: jax.Array        # [M, T]
    train_mse: jax.Array  # [M]
    train_acc: jax.Array  # [M]


def _draw(p, u):
    """Index of the first prefix sum of p [.., T] above u · total, in
    the precision of p."""
    c = jnp.cumsum(p, axis=-1)
    k = jnp.sum(c < u.astype(p.dtype)[..., None] * c[..., -1:], axis=-1)
    return jnp.minimum(k, p.shape[-1] - 1).astype(jnp.int32)


def _counts(tokens, mask, z, hp: HP, dt):
    """(ndt [D, T], ntw [T, W]) of assignments z [D, N]."""
    m = mask.astype(dt)
    ndt = (jax.nn.one_hot(z, hp.n_topics, dtype=dt) * m[..., None]).sum(1)
    ntw = jnp.zeros((hp.n_topics, hp.vocab_size), dt).at[z, tokens].add(m)
    return ndt, ntw


def _train_sweep(u, tokens, mask, y, inv_len, z, ndt, ntw, nt, eta, hp: HP,
                 dt, draw_dt):
    """One supervised sweep of one chain: scan over token positions,
    every document at once."""
    T, W = hp.n_topics, hp.vocab_size
    ntw_t = ntw.T
    iota = jnp.arange(T)

    def step(carry, col):
        ndt, s = carry
        w, m, k, uu = col
        m = m.astype(dt)
        old = (iota == k[:, None]).astype(dt) * m[:, None]
        ndt = ndt - old
        s = s - eta[k] * m
        p = (ndt + hp.alpha) * (ntw_t[w] - old + hp.beta) \
            / (nt - old + W * hp.beta)
        mu = (s[:, None] + eta) * inv_len[:, None]
        g = -((y[:, None] - mu) ** 2) / (2 * hp.rho)
        p = p * jnp.exp(g - g.max(-1, keepdims=True))
        k_new = jnp.where(m > 0, _draw(p.astype(draw_dt), uu), k)
        new = (iota == k_new[:, None]).astype(dt) * m[:, None]
        return (ndt + new, s + eta[k_new] * m), k_new

    s0 = (ndt * eta).sum(-1)
    (ndt, _), z_new = jax.lax.scan(step, (ndt, s0),
                                   (tokens.T, mask.T, z.T, u.T))
    return z_new.T, ndt


def _solve_eta(zbar, y, hp: HP, dt):
    """Ridge solve (Z̄ᵀZ̄/ρ + I/σ) η = Z̄ᵀy/ρ + μ/σ; products in `dt`."""
    T = hp.n_topics
    gram = jnp.einsum("dt,ds->ts", zbar, zbar, precision=HIGHEST,
                      preferred_element_type=dt) / hp.rho \
        + jnp.eye(T, dtype=dt) / hp.sigma
    rhs = (zbar * y[:, None]).sum(0) / hp.rho + hp.mu / hp.sigma
    eta = jnp.linalg.solve(gram.astype(jnp.float32),
                           rhs.astype(jnp.float32))
    return eta.astype(dt)


def _train_chain(key, tokens, mask, y, hp: HP, dt, draw_dt):
    D, N = tokens.shape
    k_init, k_sweeps = jax.random.split(key)
    y = y.astype(dt)
    lens = jnp.maximum(mask.sum(-1), 1.0)
    inv_len = (1.0 / lens).astype(dt)
    z = jax.random.randint(k_init, (D, N), 0, hp.n_topics, jnp.int32)
    ndt, ntw = _counts(tokens, mask, z, hp, dt)
    eta = jnp.full((hp.n_topics,), hp.mu, dt)

    def em(carry, k):
        z, ndt, ntw, eta = carry
        u = jax.random.uniform(k, (D, N))
        z, ndt = _train_sweep(u, tokens, mask, y, inv_len, z, ndt, ntw,
                              ntw.sum(-1), eta, hp, dt, draw_dt)
        ndt, ntw = _counts(tokens, mask, z, hp, dt)
        eta = _solve_eta(ndt * inv_len[:, None], y, hp, dt)
        return (z, ndt, ntw, eta), None

    (z, ndt, ntw, eta), _ = jax.lax.scan(
        em, (z, ndt, ntw, eta), jax.random.split(k_sweeps, hp.n_iters))
    phi = (ntw + hp.beta) / (ntw.sum(-1, keepdims=True)
                             + hp.vocab_size * hp.beta)
    fit = (ndt * inv_len[:, None] * eta).sum(-1).astype(jnp.float32)
    yf = y.astype(jnp.float32)
    # computed in `dtype`, handed back in float32: the program's interface
    return Models(phi=phi.astype(jnp.float32), eta=eta.astype(jnp.float32),
                  train_mse=jnp.mean((fit - yf) ** 2),
                  train_acc=jnp.mean(((fit > 0.5) == (yf > 0.5))
                                     .astype(jnp.float32)))


@functools.partial(jax.jit, static_argnames=("hp", "dtype", "draw_dtype"))
def train(keys, shards: Docs, hp: HP, dtype=jnp.float32,
          draw_dtype=None) -> Models:
    """Train M chains on their shards [M, D/M, N] from keys [M], every
    count, sum and product in `dtype` (the draws' weights in
    `draw_dtype`, if given); the models come back in float32."""
    return jax.vmap(lambda k, t, m, y: _train_chain(
        k, t, m, y, hp, dtype, draw_dtype or dtype))(
        keys, shards.tokens, shards.mask, shards.y)


def _predict_chain(key, phi, eta, tokens, mask, hp: HP, dt, draw_dt):
    D, N = tokens.shape
    T = hp.n_topics
    phi_t = phi.astype(dt).T
    iota = jnp.arange(T)
    k_init, k_sweeps = jax.random.split(key)
    z = jax.random.randint(k_init, (D, N), 0, T, jnp.int32)
    ndt, _ = _counts(tokens, mask, z, hp, dt)
    n_sweeps = hp.n_pred_burnin + hp.n_pred_samples

    def sweep(carry, inp):
        z, ndt, acc = carry
        k, i = inp
        u = jax.random.uniform(k, (D, N))

        def step(ndt, col):
            w, m, kk, uu = col
            m = m.astype(dt)
            old = (iota == kk[:, None]).astype(dt) * m[:, None]
            ndt = ndt - old
            p = (ndt + hp.alpha) * phi_t[w]
            k_new = jnp.where(m > 0, _draw(p.astype(draw_dt), uu), kk)
            return ndt + (iota == k_new[:, None]).astype(dt) * m[:, None], \
                k_new

        ndt, z_new = jax.lax.scan(step, ndt, (tokens.T, mask.T, z.T, u.T))
        acc = acc + jnp.where(i >= hp.n_pred_burnin, 1.0, 0.0).astype(dt) \
            * ndt
        return (z_new.T, ndt, acc), None

    acc0 = jnp.zeros((D, T), dt)
    (_, _, acc), _ = jax.lax.scan(
        sweep, (z, ndt, acc0),
        (jax.random.split(k_sweeps, n_sweeps), jnp.arange(n_sweeps)))
    lens = jnp.maximum(mask.sum(-1), 1.0).astype(dt)
    zbar = acc / hp.n_pred_samples / lens[:, None]
    yhat = (zbar * eta.astype(dt)).sum(-1)
    return zbar.astype(jnp.float32), yhat.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("hp", "dtype", "draw_dtype"))
def predict(keys, models: Models, docs: Docs, hp: HP, dtype=jnp.float32,
            draw_dtype=None):
    """Every chain predicts every document: (z̄ [M, D, T], ŷ [M, D]),
    accumulated in `dtype` (the draws' weights in `draw_dtype`, if
    given) and handed back in float32."""
    return jax.vmap(lambda k, phi, eta: _predict_chain(
        k, phi, eta, docs.tokens, docs.mask, hp, dtype,
        draw_dtype or dtype))(
        keys, models.phi, models.eta)


def combine_weights(stat, binary: bool) -> np.ndarray:
    """Eqs. 8-9: weights ∝ 1/MSE (continuous) or ∝ accuracy (binary)."""
    stat = np.asarray(stat, np.float64)
    raw = stat if binary else 1.0 / stat
    return raw / raw.sum()


def weights_from_predictions(yhat_train, y_train, binary: bool):
    """Each chain's full-training-set MSE or accuracy → weights."""
    yh = np.asarray(yhat_train, np.float64)
    y = np.asarray(y_train, np.float64)[None]
    stat = ((yh > 0.5) == (y > 0.5)).mean(-1) if binary \
        else ((yh - y) ** 2).mean(-1)
    return combine_weights(stat, binary)


def shards_of(docs: Docs, m: int) -> Docs:
    """Contiguous split into M equal shards [M, D/M, ...]."""
    d = docs.tokens.shape[0]
    if d % m:
        raise ValueError(f"{d} documents do not split into {m} shards")
    return Docs(*(a.reshape((m, d // m) + a.shape[1:]) for a in docs))


def weighted_average(key, train_docs: Docs, test_docs: Docs, hp: HP, m: int,
                     binary: bool, dtype=jnp.float32) -> dict:
    """The paper's Weighted Average: M chains on contiguous shards, each
    predicts the test set and the whole training set, Eqs. 8-9."""
    k_train, k_pred = jax.random.split(key)
    models = train(jax.random.split(k_train, m), shards_of(train_docs, m),
                   hp, dtype)
    both = Docs(*(jnp.concatenate([a, b]) for a, b in
                  zip(test_docs, train_docs)))
    _, yhat = predict(jax.random.split(k_pred, m), models, both, hp, dtype)
    yhat = np.asarray(yhat, np.float64)
    n_te = test_docs.tokens.shape[0]
    w = weights_from_predictions(yhat[:, n_te:], train_docs.y, binary)
    return {"models": models, "yhat": yhat, "weights": w,
            "out": w @ yhat[:, :n_te]}
