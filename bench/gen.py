"""Seeded inputs: corpora drawn from the sLDA generative process, made on
the device in one jitted call each.

A configuration fixes a "world" (true topics φ*, true regression η*) and
the corpus shape.  Every seed gets the same multiset of document lengths
(the quantiles of the configuration's log-normal), in another order, so
the work of a run does not depend on its seed; the words, the topic
assignments and the labels do.
"""
from __future__ import annotations

import functools
import statistics
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np



class Docs(NamedTuple):
    """A padded bag of documents: tokens int32 [D, N], mask float32
    [D, N] (1 on real tokens), labels y float32 [D]."""
    tokens: jax.Array
    mask: jax.Array
    y: jax.Array


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number below 2**64."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def length_profile(n_docs: int, length: dict, max_len: int) -> np.ndarray:
    """The sorted lengths of `n_docs` documents: the quantiles
    (i + 1/2) / n of LogNormal(log median, sigma), rounded and clipped
    to [min, max_len]."""
    inv = statistics.NormalDist().inv_cdf
    q = [inv((i + 0.5) / n_docs) for i in range(n_docs)]
    lens = length["median"] * np.exp(length["sigma"] * np.asarray(q))
    return np.clip(np.rint(lens), length["min"], max_len).astype(np.int32)


FIXED = 2 ** 26      # fixed-point scale of the token draw's CDFs


@functools.partial(jax.jit, static_argnames=("n_topics", "vocab_size"))
def make_world(key, *, n_topics: int, vocab_size: int, beta: float,
               eta_scale: float):
    """True topics φ* [T, W] ~ Dir(β) and regression weights η* [T]."""
    k_phi, k_eta = jax.random.split(key)
    phi = jax.random.dirichlet(k_phi, jnp.full((vocab_size,), beta),
                               (n_topics,))
    eta = jax.random.normal(k_eta, (n_topics,)) * eta_scale
    return phi, eta


@functools.partial(jax.jit, static_argnames=("max_len", "binary"))
def make_docs(key, phi, eta, lengths, *, max_len: int, alpha: float,
              rho: float, binary: bool) -> Docs:
    """Documents of the given lengths (permuted by `key`): θ_d ~ Dir(α),
    z ~ θ_d, w ~ φ*_z, y = η*ᵀ z̄ + N(0, ρ); binary labels threshold y at
    its median."""
    T, W = phi.shape
    D = lengths.shape[0]
    k_perm, k_theta, k_z, k_w, k_y = jax.random.split(key, 5)
    lens = lengths[jax.random.permutation(k_perm, D)]
    mask = (jnp.arange(max_len)[None, :] < lens[:, None]).astype(jnp.float32)
    theta = jax.random.dirichlet(k_theta, jnp.full((T,), alpha), (D,))
    z = jax.random.categorical(k_z, jnp.log(theta)[:, None, :],
                               shape=(D, max_len))
    # inverse CDF: one search of all topics' CDFs laid end to end in
    # 26-bit fixed point (topic t holds [t·2^26, (t+1)·2^26)), never a
    # [D, N, W] array and not one search per topic
    cdf = jnp.floor(jnp.cumsum(phi, axis=-1) * FIXED).astype(jnp.uint32)
    flat = (cdf + jnp.arange(T, dtype=jnp.uint32)[:, None]
            * jnp.uint32(FIXED)).ravel()
    u = jax.random.uniform(k_w, (D, max_len))
    top = cdf[z, -1].astype(jnp.float32)
    q = z.astype(jnp.uint32) * jnp.uint32(FIXED) \
        + jnp.floor(u * top).astype(jnp.uint32)
    tokens = jnp.searchsorted(flat, q, side="right").astype(jnp.int32) \
        - z * W
    tokens = jnp.clip(tokens, 0, W - 1).astype(jnp.int32)
    zbar = (jax.nn.one_hot(z, T) * mask[..., None]).sum(1) \
        / lens[:, None].astype(jnp.float32)
    y = (zbar * eta).sum(-1) + jnp.sqrt(rho) * jax.random.normal(k_y, (D,))
    if binary:
        y = (y > jnp.median(y)).astype(jnp.float32)
    return Docs(tokens=tokens, mask=mask, y=y)


def world_and_docs(seed: int, conf: dict, n_docs: int, stream: int = 0):
    """The configuration's world for `seed` and `n_docs` documents of its
    length profile.  Different `stream`s give independent documents of
    the same world."""
    key = seed_key(seed)
    phi, eta = make_world(jax.random.fold_in(key, 0),
                          n_topics=conf["n_topics"],
                          vocab_size=conf["vocab_size"], beta=conf["beta"],
                          eta_scale=conf["eta_scale"])
    lengths = jnp.asarray(length_profile(n_docs, conf["length"],
                                         conf["max_len"]))
    docs = make_docs(jax.random.fold_in(key, 1 + stream), phi, eta, lengths,
                     max_len=conf["max_len"], alpha=conf["alpha"],
                     rho=conf["rho"],
                     binary=conf["label_type"] == "binary")
    return (phi, eta), docs
