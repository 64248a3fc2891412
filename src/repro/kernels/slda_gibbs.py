"""Pallas TPU kernel for the sLDA collapsed-Gibbs sweep (the paper's hot loop).

TPU adaptation (DESIGN.md §3): the token loop is inherently sequential, but
  * the per-token categorical over T topics vectorizes onto the lane
    dimension (T = 128 fills a VREG lane exactly), and
  * a block of DOC_BLOCK documents is swept in lockstep on the sublane
    dimension — documents are independent within a sweep because the
    topic-word table is sweep-frozen (AD-LDA delayed counts).

Layout: the topic-word table is stored transposed, ``ntw_t [W, T]``, and
lives whole in VMEM (sLDA vocabularies are small — the paper's is 4238
phrases).  Per token, each document of the block reads its word's
``[1, T]`` row with a dynamic sublane load addressed from an SMEM copy of
the block's word ids (`access.gather_rows`; DESIGN.md §Predict-kernel,
"Row access on the chip").

Grid: (D / DOC_BLOCK,).  One grid cell sweeps DOC_BLOCK documents
end-to-end and writes back their new assignments and doc-topic counts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.mathutil import upper_tri_ones
from .access import (check_compiled_mode, column, gather_rows, pick,
                     set_column)
from .sparse import (build_topic_index, gather_index_rows,
                     sparse_two_stage_draw)


def _gibbs_kernel(tokens_ref, mask_ref, unif_ref, z_ref, ndt_ref,
                  y_ref, invlen_ref, ntw_t_ref, nt_ref, eta_ref, *refs,
                  alpha: float, beta: float, rho: float,
                  supervised: bool, n_tokens: int, vocab_size: int,
                  sampler_mode: str = "dense"):
    # tokens_ref holds the block's word ids in SMEM (row addresses for
    # gather_rows).  Sparse mode appends the three sweep-frozen
    # topic-index inputs and runs interpreted only; unpacking on the
    # static mode keeps the dense trace byte-identical
    if sampler_mode == "sparse":
        idx_ref, vmask_ref, occm_ref, z_out_ref, ndt_out_ref, rows_ref = refs
    else:
        z_out_ref, ndt_out_ref, rows_ref = refs
    eta = eta_ref[0, :]                       # [T]
    nt = nt_ref[0, :]                         # [T]
    y = y_ref[:, 0]                           # [DB]
    inv_len = invlen_ref[:, 0]                # [DB]
    mask, unif, z = mask_ref[...], unif_ref[...], z_ref[...]   # [DB, N]
    T = eta.shape[0]
    topic_iota = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    tri_u = upper_tri_ones(T)   # prefix-sum-as-matmul (see slda_predict.py)

    ndt0 = ndt_ref[...]                       # [DB, T]
    s0 = ndt0 @ eta                           # [DB]  running Σ_t η_t N_dt
    z_out_ref[...] = z

    def token_step(n, carry):
        ndt, s = carry
        m = column(mask, n)                   # [DB]
        u = column(unif, n)                   # [DB]
        z_old = column(z, n)                  # [DB]

        own = topic_iota == z_old[:, None]
        old = own.astype(jnp.float32) * m[:, None]
        ndt = ndt - old
        s = s - pick(eta, own) * m

        ntw_w = gather_rows(ntw_t_ref, tokens_ref, n, rows_ref) - old
        logp = (jnp.log(ndt + alpha)
                + jnp.log(ntw_w + beta)
                - jnp.log(nt[None, :] - old + vocab_size * beta))
        if supervised:
            mu_t = (s[:, None] + eta[None, :]) * inv_len[:, None]
            logp = logp - 0.5 * (y[:, None] - mu_t) ** 2 / rho

        p = jnp.exp(logp - jnp.max(logp, axis=1, keepdims=True))
        if sampler_mode == "sparse":
            w = column(tokens_ref[...], n)
            z_new = sparse_two_stage_draw(
                p, u, *gather_index_rows(w, idx_ref[...], vmask_ref[...],
                                         occm_ref[...]))
        else:
            c = jnp.dot(p, tri_u)
            z_new = jnp.sum(
                (c < (u * c[:, T - 1])[:, None]).astype(jnp.int32), axis=1)
        z_new = jnp.where(m > 0, z_new, z_old).astype(jnp.int32)

        new = topic_iota == z_new[:, None]
        ndt = ndt + new.astype(jnp.float32) * m[:, None]
        s = s + pick(eta, new) * m
        set_column(z_out_ref, n, z_new)
        return ndt, s

    ndt, _ = jax.lax.fori_loop(0, n_tokens, token_step, (ndt0, s0))
    ndt_out_ref[...] = ndt


def slda_gibbs_sweep_pallas(tokens, mask, uniforms, z, ndt, y, inv_len,
                            ntw_t, nt, eta, *, alpha, beta, rho,
                            supervised=True, doc_block=8, interpret=True,
                            sampler_mode="dense", sparse_topic_cap=32,
                            topic_index=None):
    """Blocked document-parallel Gibbs sweep.  Shapes as in ref.py.

    D must be a multiple of doc_block (ops.py pads).  Returns (z_new, ndt_new).
    sampler_mode="sparse" routes the draw through the two-stage sparse
    draw against the per-word topic index of the sweep-frozen `ntw_t`
    (built here unless passed pre-built as `topic_index`).
    """
    check_compiled_mode(sampler_mode, interpret)
    D, N = tokens.shape
    T = ndt.shape[-1]
    W = ntw_t.shape[0]
    assert D % doc_block == 0, (D, doc_block)
    grid = (D // doc_block,)

    doc_spec = lambda cols: pl.BlockSpec((doc_block, cols), lambda i: (i, 0))
    full = lambda shape: pl.BlockSpec(shape, lambda i: tuple(0 for _ in shape))

    kernel = functools.partial(
        _gibbs_kernel, alpha=float(alpha), beta=float(beta), rho=float(rho),
        supervised=supervised, n_tokens=N, vocab_size=W,
        sampler_mode=sampler_mode)

    ids_spec = pl.BlockSpec((doc_block, N), lambda i: (i, 0),
                            memory_space=pltpu.SMEM)
    in_specs = [ids_spec, doc_spec(N), doc_spec(N), doc_spec(N),
                doc_spec(T), doc_spec(1), doc_spec(1),
                full((W, T)), full((1, T)), full((1, T))]
    operands = [tokens, mask, uniforms, z, ndt, y[:, None],
                inv_len[:, None], ntw_t, nt[None, :], eta[None, :]]
    if sampler_mode == "sparse":
        if topic_index is None:
            topic_index = build_topic_index(ntw_t, sparse_topic_cap)
        cap = topic_index[0].shape[-1]
        in_specs += [full((W, cap)), full((W, cap)), full((W, T))]
        operands += list(topic_index)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[doc_spec(N), doc_spec(T)],
        out_shape=[jax.ShapeDtypeStruct((D, N), jnp.int32),
                   jax.ShapeDtypeStruct((D, T), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((doc_block, T), jnp.float32)],
        interpret=interpret,
    )(*operands)
