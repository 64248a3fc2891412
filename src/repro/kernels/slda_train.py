"""Pallas TPU kernel for fused multi-sweep sLDA *training* launches.

PR 1 fused all prediction sweeps into one launch (slda_predict.py); this
module does the same for training, the other half of every chain's
wall-clock.  The seed training loop pays, per sweep: one kernel launch,
one `[D, N]` threefry uniforms materialization, and one host-visible
count refresh.  The fused path amortizes all three over
``n_sweeps = SLDAConfig.sweeps_per_launch`` Gibbs sweeps per launch.

What carries over from the predict kernel (DESIGN.md §Predict-kernel):

  * counter-hash PRNG — per-token uniforms from a murmur3-style mix of
    (doc_seed, sweep·N + n), shared bit-for-bit by kernel / jnp twin /
    oracle through `train_uniforms` (same contract as `predict_uniforms`);
  * transposed `[W, T]` layout for the topic-word table, read one row
    per document and token (DESIGN.md §Predict-kernel, "Row access on
    the chip");
  * matmul prefix sums (`p @ U`, U upper-triangular ones) for the
    inverse-CDF categorical.

What is new — **in-kernel delayed-count refresh** (DESIGN.md
§Train-kernel): unlike prediction, training must refresh `ntw`/`nt`
between sweeps.  DESIGN.md §3's AD-LDA delayed-count argument already
treats the table as *stale within a sweep* and exact afterwards; the same
argument licenses keeping a block-local copy of the table in VMEM scratch
and applying the block's own ±1 deltas between the sweeps of one launch:

  * within a sweep the table is frozen (sweep-frozen lockstep documents,
    exactly the seed semantics);
  * between sweeps each `doc_block` applies ITS OWN documents' deltas to
    its local copy — exact per block, delayed across blocks until the
    launch ends and the plan recounts the exact global tables from
    z_final;
  * the refresh adds each moved token's ±1 topic delta to its word's
    table row, document by document (`access.add_rows`, the same SMEM
    word ids and dynamic row access as the per-token reads); a
    `pl.when` skips a token position whenever no document in the block
    moved it (Magnusson et al.: late in sampling nearly all tokens are
    unchanged).  All adds are 0/±1 integers far below 2^24, so the
    totals are EXACT and bit-identical to the twin's and oracle's
    scatter-adds regardless of order.

**Sampling form** — two, selected by ``product_form``:

  * log form (``product_form=False``, the seed semantics): p ∝
    exp(log(N_dt+α) + log(N_tw+β) − log(N_t+Wβ) − (y−μ)²/2ρ − max).
    `n_sweeps=1` launches keep this form so a single-sweep launch is
    exactly one seed-semantics sweep (bitwise: tests/test_train_kernel.py
    asserts agreement with the single-sweep `slda_gibbs` kernel under
    shared uniforms).
  * product form (``product_form=True``, the multi-sweep default):
    p ∝ (N_dt+α)·(N_tw+β)/(N_t+Wβ) · exp(g − max g) with
    g = −(y−μ_t)²/2ρ — the same categorical distribution (the inverse
    CDF normalizes away the scale) sampled from one `exp` per token
    instead of three `log`s, exactly how the predict kernel already
    samples its (unsupervised) product of positives.  Multi-sweep
    launches are already their own sampler family (counter-hash PRNG,
    block-delayed counts — statistically equivalent, not bit-equal to
    seed), so the cheaper form changes no contract; kernel, twin and
    oracle share it bit-for-bit.

Grids: ``(D/doc_block,)`` single-chain, ``(M, D/doc_block)`` in the
chain-batched form (`slda_train_sweeps_chains_pallas`): the leading grid
dimension walks the M independent chains of the paper's parallel
algorithms, each grid cell reading ITS chain's `ntw/nt/eta/seed` blocks
(`None`-squeezed BlockSpecs).  `ref.ref_slda_train_sweeps` is the oracle;
`slda_train_sweeps_jnp` below is the bit-identical blocked-jnp CPU fast
path (what the benchmarks measure on this container) and
`slda_train_sweeps_chains_jnp` its chain-batched form.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.mathutil import upper_tri_ones
from .access import (add_rows, check_compiled_mode, column, gather_rows,
                     pick, set_column)
from .slda_predict import (_GOLDEN, _MIX1, _MIX2, bits_to_uniform,
                           counter_uniform)
from .slda_predict import predict_uniforms as _uniforms_tensor
from .sparse import (build_topic_index, gather_index_rows,
                     sparse_two_stage_draw)


def train_uniforms(seeds, n_sweeps: int, n_tokens: int,
                   ctr_stride: int | None = None):
    """Materialize the [D, n_sweeps, N] uniforms the fused train paths
    derive on the fly — the shared-uniforms contract for driving the ref
    oracle (and the seed single-sweep path) in equivalence tests.  Same
    counter layout (and ctr_stride semantics) as `predict_uniforms`;
    never used in production."""
    return _uniforms_tensor(seeds, n_sweeps, n_tokens, ctr_stride)


def _train_kernel(tokens_ref, mask_ref, seed_ref, z_ref, ndt_ref, y_ref,
                  invlen_ref, ntw_t_ref, nt_ref, eta_ref, *refs,
                  alpha: float, beta: float, rho: float, supervised: bool,
                  n_sweeps: int, n_tokens: int, ctr_stride: int,
                  vocab_size: int, tpu_prng: bool, product_form: bool,
                  chain_grid: bool, sampler_mode: str = "dense"):
    # tokens_ref holds the block's word ids in SMEM: the row addresses of
    # the per-token table reads and of the refresh's row adds; rows_ref
    # is the [DB, T] staging buffer of both (access.py).  Sparse
    # mode appends three LAUNCH-frozen topic-index inputs (built by the
    # wrapper from the entry table — in-launch count evolution never
    # rebuilds them; exactness does not depend on index freshness) and
    # runs interpreted only.  Unpacking on the static mode keeps the
    # dense trace byte-identical.
    if sampler_mode == "sparse":
        (idx_ref, vmask_ref, occm_ref,
         z_out_ref, ndt_out_ref, ntw_scratch, rows_ref) = refs
    else:
        z_out_ref, ndt_out_ref, ntw_scratch, rows_ref = refs
    eta = eta_ref[0, :]                       # [T]
    seeds = seed_ref[:, 0]                    # [DB]
    y = y_ref[:, 0]                           # [DB]
    inv_len = invlen_ref[:, 0]                # [DB]
    mask = mask_ref[...]                      # [DB, N]
    T = eta.shape[0]
    topic_iota = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    tri_u = upper_tri_ones(T)

    if tpu_prng:
        # one hardware stream per doc block, murmur-mixed with the
        # (flattened) grid index (same caveats as the predict kernel: the
        # per-DOCUMENT seed contract holds only on the portable hash path)
        pid = pl.program_id(0)
        if chain_grid:
            pid = pid * pl.num_programs(1) + pl.program_id(1)
        mixed = seed_ref[0, 0].astype(jnp.uint32) ^ (
            pid.astype(jnp.uint32) * _GOLDEN)
        mixed = (mixed ^ (mixed >> 16)) * _MIX1
        mixed = (mixed ^ (mixed >> 13)) * _MIX2
        pltpu.prng_seed((mixed ^ (mixed >> 16)).astype(jnp.int32))

    ntw_scratch[...] = ntw_t_ref[...]         # [W, T] block-local copy
    z_out_ref[...] = z_ref[...]               # z persists across sweeps

    def sweep_body(s, carry):
        # ntw_scratch is frozen for the sweep: the refresh below runs
        # after the token loop
        ndt_start, nt = carry                 # [DB, T], [T] sweep-frozen
        z_prev = z_out_ref[...]               # [DB, N] sweep-start z
        s0 = ndt_start @ eta                  # [DB] running Σ_t η_t N_dt

        def token_step(n, carry2):
            ndt, st = carry2
            m = column(mask, n)               # [DB]
            z_old = column(z_out_ref[...], n)  # [DB]
            if tpu_prng:
                u = bits_to_uniform(pltpu.bitcast(
                    pltpu.prng_random_bits((m.shape[0], 1)),
                    jnp.uint32))[:, 0]
            else:
                u = counter_uniform(seeds, s * ctr_stride + n)

            own = topic_iota == z_old[:, None]
            old = own.astype(jnp.float32) * m[:, None]
            ndt = ndt - old
            st = st - pick(eta, own) * m

            ntw_w = gather_rows(ntw_scratch, tokens_ref, n, rows_ref) \
                - old                                   # [DB, T], -dn exact
            if product_form:
                p = (ndt + alpha) * (ntw_w + beta) \
                    / (nt[None, :] - old + vocab_size * beta)
                if supervised:
                    mu_t = (st[:, None] + eta[None, :]) * inv_len[:, None]
                    g = -0.5 * (y[:, None] - mu_t) ** 2 / rho
                    p = p * jnp.exp(g - jnp.max(g, axis=1, keepdims=True))
            else:
                logp = (jnp.log(ndt + alpha)
                        + jnp.log(ntw_w + beta)
                        - jnp.log(nt[None, :] - old + vocab_size * beta))
                if supervised:
                    mu_t = (st[:, None] + eta[None, :]) * inv_len[:, None]
                    logp = logp - 0.5 * (y[:, None] - mu_t) ** 2 / rho
                p = jnp.exp(logp - jnp.max(logp, axis=1, keepdims=True))

            if sampler_mode == "sparse":
                # two-stage sparse draw; the rare stage-2 correction is
                # predicated inside (lax.cond — the value-returning form
                # of pl.when, bitwise-equal to the branch-free select)
                w = column(tokens_ref[...], n)
                z_new = sparse_two_stage_draw(
                    p, u, *gather_index_rows(w, idx_ref[...],
                                             vmask_ref[...], occm_ref[...]))
            else:
                c = jnp.dot(p, tri_u)                   # prefix sums
                z_new = jnp.sum(
                    (c < (u * c[:, T - 1])[:, None]).astype(jnp.int32),
                    axis=1)
            z_new = jnp.where(m > 0, z_new, z_old).astype(jnp.int32)

            new = topic_iota == z_new[:, None]
            ndt = ndt + new.astype(jnp.float32) * m[:, None]
            st = st + pick(eta, new) * m
            set_column(z_out_ref, n, z_new)
            return ndt, st

        ndt, _ = jax.lax.fori_loop(0, n_tokens, token_step, (ndt_start, s0))

        # block-local delayed-count refresh: for each token position the
        # block's ±1 topic deltas are added to the table rows of the
        # block's words, document by document (repeated words accumulate)
        # — 0/±1 integer adds ≪ 2^24, so the totals are EXACT and
        # order-independent (bit-identical to the twin's and oracle's
        # scatter-adds).  Skipped after the final sweep (the local table
        # is not an output) and — per token — whenever no document in
        # the block moved (the common case late in sampling).
        @pl.when(s < n_sweeps - 1)
        def _refresh():
            z_cur = z_out_ref[...]

            def refresh_token(n, _):
                zo = column(z_prev, n)
                zn = column(z_cur, n)
                moved = (zo != zn) & (column(mask, n) > 0)

                @pl.when(jnp.any(moved))
                def _rows():
                    mv = moved.astype(jnp.float32)            # [DB]
                    rows_ref[...] = (
                        (topic_iota == zn[:, None]).astype(jnp.float32)
                        - (topic_iota == zo[:, None]).astype(jnp.float32)
                    ) * mv[:, None]                           # [DB, T]
                    add_rows(ntw_scratch, tokens_ref, n, rows_ref)
                return 0
            jax.lax.fori_loop(0, n_tokens, refresh_token, 0)

        # Δnt is the column-sum of the block's ndt deltas — exact, no
        # per-token work (±1.0 f32 adds are lossless at these magnitudes)
        return ndt, nt + jnp.sum(ndt - ndt_start, axis=0)

    ndt_final, _ = jax.lax.fori_loop(0, n_sweeps, sweep_body,
                                     (ndt_ref[...], nt_ref[0, :]))
    ndt_out_ref[...] = ndt_final


def slda_train_sweeps_pallas(tokens, mask, seeds, z0, ndt0, y, inv_len,
                             ntw_t, nt, eta, *, alpha, beta, rho,
                             supervised=True, n_sweeps=1, doc_block=8,
                             interpret=True, tpu_prng=False,
                             product_form=False, ctr_stride=None,
                             sampler_mode="dense", sparse_topic_cap=32,
                             topic_index=None):
    """All `n_sweeps` training sweeps for a doc block in ONE launch.

    tokens/mask/z0: [D, N]; seeds: int32 [D]; ndt0: [D, T]; y/inv_len: [D];
    ntw_t: [W, T] (row-gather layout); nt/eta: [T].  D must be a multiple
    of doc_block (ops.py pads).  Returns (z_final [D, N], ndt_final [D, T]);
    the caller refreshes the global tables from (z0, z_final).
    ctr_stride pins the PRNG counter stride (default N — see
    slda_predict.predict_uniforms).  sampler_mode="sparse" routes the
    per-token draw through the two-stage sparse draw against a
    launch-frozen per-word topic index (built here from `ntw_t`, or
    passed pre-built as `topic_index=(idx, vmask, occm)`).
    """
    check_compiled_mode(sampler_mode, interpret)
    D, N = tokens.shape
    T = ndt0.shape[-1]
    W = ntw_t.shape[0]
    assert D % doc_block == 0, (D, doc_block)
    grid = (D // doc_block,)

    doc_spec = lambda cols: pl.BlockSpec((doc_block, cols), lambda i: (i, 0))
    full = lambda shape: pl.BlockSpec(shape, lambda i: tuple(0 for _ in shape))

    kernel = functools.partial(
        _train_kernel, alpha=float(alpha), beta=float(beta), rho=float(rho),
        supervised=supervised, n_sweeps=int(n_sweeps), n_tokens=N,
        ctr_stride=int(N if ctr_stride is None else ctr_stride),
        vocab_size=W, tpu_prng=tpu_prng, product_form=product_form,
        chain_grid=False, sampler_mode=sampler_mode)

    ids_spec = pl.BlockSpec((doc_block, N), lambda i: (i, 0),
                            memory_space=pltpu.SMEM)
    in_specs = [ids_spec, doc_spec(N), doc_spec(1), doc_spec(N),
                doc_spec(T), doc_spec(1), doc_spec(1),
                full((W, T)), full((1, T)), full((1, T))]
    operands = [tokens, mask, seeds[:, None], z0, ndt0, y[:, None],
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
        scratch_shapes=[pltpu.VMEM((W, T), jnp.float32),
                        pltpu.VMEM((doc_block, T), jnp.float32)],
        interpret=interpret,
    )(*operands)


def slda_train_sweeps_chains_pallas(tokens, mask, seeds, z0, ndt0, y,
                                    inv_len, ntw_t, nt, eta, *, alpha, beta,
                                    rho, supervised=True, n_sweeps=1,
                                    doc_block=8, interpret=True,
                                    tpu_prng=False, product_form=False,
                                    ctr_stride=None, sampler_mode="dense",
                                    sparse_topic_cap=32, topic_index=None):
    """Chain-batched fused train launch: grid (M, D/doc_block).

    One pallas_call runs all M independent chains: tokens/mask/z0
    [M, D, N]; seeds [M, D]; ndt0 [M, D, T]; y/inv_len [M, D]; ntw_t
    [M, W, T]; nt/eta [M, T].  The leading grid dimension selects the
    chain; every per-chain input is carved with a `None`-squeezed
    BlockSpec so the kernel body is EXACTLY `_train_kernel` — same ops,
    same order, bit-identical per chain to the single-chain launch.
    Returns (z_final [M, D, N], ndt_final [M, D, T]).
    """
    check_compiled_mode(sampler_mode, interpret)
    M, D, N = tokens.shape
    T = ndt0.shape[-1]
    W = ntw_t.shape[1]
    assert D % doc_block == 0, (D, doc_block)
    grid = (M, D // doc_block)

    cdoc = lambda cols: pl.BlockSpec((None, doc_block, cols),
                                     lambda c, i: (c, i, 0))
    cfull = lambda shape: pl.BlockSpec(
        (None,) + shape, lambda c, i: (c,) + tuple(0 for _ in shape))

    kernel = functools.partial(
        _train_kernel, alpha=float(alpha), beta=float(beta), rho=float(rho),
        supervised=supervised, n_sweeps=int(n_sweeps), n_tokens=N,
        ctr_stride=int(N if ctr_stride is None else ctr_stride),
        vocab_size=W, tpu_prng=tpu_prng, product_form=product_form,
        chain_grid=True, sampler_mode=sampler_mode)

    ids_spec = pl.BlockSpec((None, doc_block, N), lambda c, i: (c, i, 0),
                            memory_space=pltpu.SMEM)
    in_specs = [ids_spec, cdoc(N), cdoc(1), cdoc(N), cdoc(T), cdoc(1),
                cdoc(1), cfull((W, T)), cfull((1, T)), cfull((1, T))]
    operands = [tokens, mask, seeds[..., None], z0, ndt0, y[..., None],
                inv_len[..., None], ntw_t, nt[:, None, :], eta[:, None, :]]
    if sampler_mode == "sparse":
        if topic_index is None:
            topic_index = build_topic_index(ntw_t, sparse_topic_cap)
        cap = topic_index[0].shape[-1]
        in_specs += [cfull((W, cap)), cfull((W, cap)), cfull((W, T))]
        operands += list(topic_index)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[cdoc(N), cdoc(T)],
        out_shape=[jax.ShapeDtypeStruct((M, D, N), jnp.int32),
                   jax.ShapeDtypeStruct((M, D, T), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((W, T), jnp.float32),
                        pltpu.VMEM((doc_block, T), jnp.float32)],
        interpret=interpret,
    )(*operands)


def slda_train_sweeps_jnp(tokens, mask, seeds, z0, ndt0, y, inv_len,
                          ntw_t, nt, eta, *, alpha, beta, rho,
                          supervised=True, n_sweeps=1, doc_block=8,
                          unroll=8, product_form=False, ctr_stride=None,
                          sampler_mode="dense", sparse_topic_cap=32,
                          topic_index=None):
    """Blocked-jnp twin of the fused train kernel — the CPU fast path.

    Same restructuring expressed as XLA-friendly jnp: a vmap over doc
    blocks, each block's documents advancing in lockstep (one [DB, T]
    vector op per token, identical op order to the kernel so the bits
    match), the token scan unrolled ×8, and the block-local between-sweep
    refresh as a scalar 2-scatter over the block's tokens (same exact
    integer arithmetic as the kernel's row adds, so the tables agree
    bit-for-bit regardless of accumulation order).

    In product form (the multi-sweep default) the per-token work is one
    row gather + one `exp`, mirroring the kernel verbatim.  The log form
    (seed semantics, `n_sweeps=1` launches) keeps two twin-only rewrites
    that cut the CPU transcendental count while preserving the bits:

      * hoisted log tables — `log(ntw+β)` / `log(nt+Wβ)` are sweep-frozen,
        so they are computed ONCE per sweep ([W, T] + [T] logs) and row-
        gathered per token; the only entry the -dn exclusion touches is
        the document's own (w, z_old) cell, which gets a scalar fixup
        `log((v-1)+β)`.  Bitwise-safe because `(v - 0.0) + β ≡ v + β` in
        IEEE f32, so every element equals the kernel's
        `log((v - old) + β)` exactly;
      * the token loop is a `lax.scan` unrolled ×8 (dispatch-bound).

    Memory: each block carries its own [W, T] count copy (plus a log-table
    copy in log form), so the live footprint is ~2·(D/doc_block)·W·T
    floats — larger doc_block is both faster (fewer vmap lanes) and *less*
    delayed (fewer blocks); core.gibbs clamps it to the corpus size.
    """
    D, N = tokens.shape
    if ctr_stride is None:
        ctr_stride = N
    T = ndt0.shape[-1]
    W = ntw_t.shape[0]
    assert D % doc_block == 0, (D, doc_block)
    B = D // doc_block
    topic_iota = jnp.arange(T, dtype=jnp.int32)[None, :]
    tri_u = upper_tri_ones(T)
    n_iota = jnp.arange(N, dtype=jnp.int32)
    # sparse mode: LAUNCH-frozen index from the entry table, shared by
    # all blocks — exactly the kernel's extra-input contract
    if sampler_mode == "sparse" and topic_index is None:
        topic_index = build_topic_index(ntw_t, sparse_topic_cap)
    s_idx, s_vm, s_om = topic_index if topic_index is not None else (
        None, None, None)

    blk = lambda a: a.reshape((B, doc_block) + a.shape[1:])

    def block_fn(tok_b, mask_b, seed_b, z_b, ndt_b, y_b, il_b):
        tok_t = tok_b.T                        # [N, DB] token-major for scan
        mask_t = mask_b.T
        w_flat = tok_b.ravel()                 # [DB*N] for the refresh

        def one_sweep(carry, s, refresh=True):
            z_t, ndt_start, ntw_loc, nt_loc = carry
            s0 = ndt_start @ eta
            if not product_form:
                # sweep-frozen hoisted log tables (see docstring: bit-equal
                # to the kernel's per-token logs as (v - 0.0) + β ≡ v + β)
                log_ntw = jnp.log(ntw_loc + beta)      # [W, T]
                log_nt = jnp.log(nt_loc + W * beta)    # [T]

            def token_step(carry2, inp):
                ndt, st = carry2
                w, m, z_old, n = inp
                u = counter_uniform(seed_b, s * ctr_stride + n)
                own = (topic_iota == z_old[:, None]) & (m[:, None] > 0)
                old = own.astype(jnp.float32)
                ndt = ndt - old
                st = st - jnp.take(eta, z_old) * m
                if product_form:
                    ntw_w = jnp.take(ntw_loc, w, axis=0) - old
                    p = (ndt + alpha) * (ntw_w + beta) \
                        / (nt_loc[None, :] - old + W * beta)
                    if supervised:
                        mu_t = (st[:, None] + eta[None, :]) * il_b[:, None]
                        g = -0.5 * (y_b[:, None] - mu_t) ** 2 / rho
                        p = p * jnp.exp(g - jnp.max(g, axis=1, keepdims=True))
                else:
                    # own-token -dn fixups: one scalar log per document
                    v_own = ntw_loc[w, z_old]          # [DB]
                    fix_ntw = jnp.log((v_own - 1.0) + beta)
                    fix_nt = jnp.log((jnp.take(nt_loc, z_old) - 1.0)
                                     + W * beta)
                    lw = jnp.where(own, fix_ntw[:, None],
                                   jnp.take(log_ntw, w, axis=0))
                    ln = jnp.where(own, fix_nt[:, None], log_nt[None, :])
                    logp = jnp.log(ndt + alpha) + lw - ln
                    if supervised:
                        mu_t = (st[:, None] + eta[None, :]) * il_b[:, None]
                        logp = logp - 0.5 * (y_b[:, None] - mu_t) ** 2 / rho
                    p = jnp.exp(logp - jnp.max(logp, axis=1, keepdims=True))
                if sampler_mode == "sparse":
                    z_new = sparse_two_stage_draw(
                        p, u, jnp.take(s_idx, w, axis=0),
                        jnp.take(s_vm, w, axis=0),
                        jnp.take(s_om, w, axis=0))
                else:
                    c = jnp.dot(p, tri_u)
                    z_new = jnp.sum(
                        (c < (u * c[:, -1])[:, None]).astype(jnp.int32),
                        axis=1)
                z_new = jnp.where(m > 0, z_new, z_old).astype(jnp.int32)
                ndt = ndt + (topic_iota == z_new[:, None]) \
                    .astype(jnp.float32) * m[:, None]
                st = st + jnp.take(eta, z_new) * m
                return (ndt, st), z_new

            (ndt, _), z_t_new = jax.lax.scan(
                token_step, (ndt_start, s0), (tok_t, mask_t, z_t, n_iota),
                unroll=unroll)

            # block-local delayed-count refresh: scalar ±1 2-scatter over
            # the block's changed tokens (exact; see module docstring).
            # Skipped after the final sweep — the tables are not outputs —
            # mirroring the kernel's pl.when (bits unchanged)
            if refresh:
                zo = z_t.T.ravel()
                zn = z_t_new.T.ravel()
                changed = mask_b.ravel() * (zn != zo).astype(jnp.float32)
                ntw_loc = (ntw_loc.at[w_flat, zo].add(-changed)
                           .at[w_flat, zn].add(changed))
                nt_loc = nt_loc + jnp.sum(ndt - ndt_start, axis=0)
            return (z_t_new, ndt, ntw_loc, nt_loc), None

        carry = (z_b.T, ndt_b, ntw_t, nt)
        if n_sweeps > 1:
            carry, _ = jax.lax.scan(
                one_sweep, carry, jnp.arange(n_sweeps - 1, dtype=jnp.int32))
        (z_t, ndt_b, _, _), _ = one_sweep(
            carry, jnp.int32(n_sweeps - 1), refresh=False)
        return z_t.T, ndt_b

    z_fin, ndt_fin = jax.vmap(block_fn)(
        blk(tokens), blk(mask), blk(seeds), blk(z0), blk(ndt0), blk(y),
        blk(inv_len))
    return (z_fin.reshape(D, N).astype(jnp.int32),
            ndt_fin.reshape(D, T))


def slda_train_stair_jnp(seg_tokens, seg_mask, seg_z0, seg_row_start,
                         seg_tok_start, seeds, ndt0, y, inv_len,
                         ntw_t_stack, nt, eta, chain_of_row, *, alpha,
                         beta, rho, vocab_size, ctr_stride,
                         supervised=True, n_sweeps=1, product_form=False,
                         unroll=8, sampler_mode="dense",
                         sparse_topic_cap=32, topic_index=None):
    """STAIRCASE fused-training twin — the ragged layer's CPU executor
    for multi-sweep launches (DESIGN.md §Ragged-execution).

    Same stair walk as `slda_predict_stair_jnp`: docs sorted ASCENDING
    by length, chains folded DOC-MAJOR (row r = d·M + c) so each token
    segment [w_{k-1}, w_k) runs on the still-alive row SUFFIX — the
    sequential step count per sweep stays N_max while executed slots
    collapse to the staircase.  Chains fold around ONE stacked
    `[M·W, T]` topic-word table (token ids pre-offset by `c·W`) exactly
    like the prediction fold; the per-chain `nt`/η are row-gathered once
    per sweep (both sweep-frozen).

    Between in-launch sweeps the table refreshes from ALL rows' changed
    tokens — the block partition here is the WHOLE corpus, i.e. the
    doc_block→D limit of the §Train-kernel delayed-count family (least
    delayed; the per-sweep refresh is exact globally, like the seed
    path's between-sweep refresh, while the counter-hash PRNG and the
    in-launch frozen η keep it a fused-family member).  As everywhere,
    at n_sweeps=1 no refresh runs and per-document results are
    bit-identical to the padded op under any schedule.

    seg_tokens/seg_mask/seg_z0: per-segment [R_k, L_k] (tokens
    pre-offset into the stacked vocab); seeds/y/inv_len: [R] folded;
    ndt0: [R, T]; ntw_t_stack: [M·W, T]; nt/eta: [M, T];
    chain_of_row: int32 [R].  Returns (z_segs_final, ndt_final [R, T]);
    the caller refreshes the global tables from (z0, z_final).
    """
    R, T = ndt0.shape
    W = vocab_size
    topic_iota = jnp.arange(T, dtype=jnp.int32)[None, :]
    tri_u = upper_tri_ones(T)
    eta_rows = jnp.take(eta, chain_of_row, axis=0)        # [R, T] frozen
    # sparse mode: launch-frozen index over the STACKED [M·W, T] table —
    # row c·W + w matches the per-chain tables bit-for-bit, so the draw
    # agrees with the blocks executor under the same uniforms
    if sampler_mode == "sparse" and topic_index is None:
        topic_index = build_topic_index(ntw_t_stack, sparse_topic_cap)
    s_idx, s_vm, s_om = topic_index if topic_index is not None else (
        None, None, None)
    segs = []
    for tok, mk, r0, n0 in zip(seg_tokens, seg_mask, seg_row_start,
                               seg_tok_start):
        L = tok.shape[-1]
        n_iota = jnp.arange(n0, n0 + L, dtype=jnp.int32)
        segs.append((tok.T, mk.T, int(r0), n_iota))       # token-major
    z_init = tuple(z.T for z in seg_z0)

    def one_sweep(carry, s, refresh=True):
        z_segs, ndt_start, ntw_loc, nt_loc = carry
        nt_rows = jnp.take(nt_loc, chain_of_row, axis=0)  # [R, T] frozen
        st0 = jnp.sum(ndt_start * eta_rows, axis=-1)      # [R]
        if not product_form:
            # sweep-frozen hoisted log tables + own-token scalar fixups
            # (bit-equal to per-token logs — see slda_train_sweeps_jnp)
            log_ntw = jnp.log(ntw_loc + beta)             # [M·W, T]
            log_nt_rows = jnp.log(nt_rows + W * beta)     # [R, T]
        ndt, st = ndt_start, st0
        new_z = []
        for (tok_t, mask_t, r0, n_iota), z_t in zip(segs, z_segs):
            sub = lambda a: a[r0:] if r0 else a
            seeds_s, y_s, il_s = sub(seeds), sub(y), sub(inv_len)
            eta_s, nt_rows_s = sub(eta_rows), sub(nt_rows)
            if not product_form:
                log_nt_s = sub(log_nt_rows)
            take_eta = lambda zz: jnp.take_along_axis(
                eta_s, zz[:, None], axis=1)[:, 0]

            def token_step(carry2, inp):
                nd, stt = carry2
                w, m, z_old, n = inp
                u = counter_uniform(seeds_s, s * ctr_stride + n)
                own = (topic_iota == z_old[:, None]) & (m[:, None] > 0)
                old = own.astype(jnp.float32)
                nd = nd - old
                stt = stt - take_eta(z_old) * m
                if product_form:
                    ntw_w = jnp.take(ntw_loc, w, axis=0) - old
                    p = (nd + alpha) * (ntw_w + beta) \
                        / (nt_rows_s - old + W * beta)
                    if supervised:
                        mu_t = (stt[:, None] + eta_s) * il_s[:, None]
                        g = -0.5 * (y_s[:, None] - mu_t) ** 2 / rho
                        p = p * jnp.exp(g - jnp.max(g, axis=1,
                                                    keepdims=True))
                else:
                    v_own = ntw_loc[w, z_old]             # [Rk]
                    fix_ntw = jnp.log((v_own - 1.0) + beta)
                    nt_own = jnp.take_along_axis(
                        nt_rows_s, z_old[:, None], axis=1)[:, 0]
                    fix_nt = jnp.log((nt_own - 1.0) + W * beta)
                    lw = jnp.where(own, fix_ntw[:, None],
                                   jnp.take(log_ntw, w, axis=0))
                    ln = jnp.where(own, fix_nt[:, None], log_nt_s)
                    logp = jnp.log(nd + alpha) + lw - ln
                    if supervised:
                        mu_t = (stt[:, None] + eta_s) * il_s[:, None]
                        logp = logp - 0.5 * (y_s[:, None] - mu_t) ** 2 \
                            / rho
                    p = jnp.exp(logp - jnp.max(logp, axis=1,
                                               keepdims=True))
                if sampler_mode == "sparse":
                    z_new = sparse_two_stage_draw(
                        p, u, jnp.take(s_idx, w, axis=0),
                        jnp.take(s_vm, w, axis=0),
                        jnp.take(s_om, w, axis=0))
                else:
                    c = jnp.dot(p, tri_u)
                    z_new = jnp.sum(
                        (c < (u * c[:, -1])[:, None]).astype(jnp.int32),
                        axis=1)
                z_new = jnp.where(m > 0, z_new, z_old).astype(jnp.int32)
                nd = nd + (topic_iota == z_new[:, None]) \
                    .astype(jnp.float32) * m[:, None]
                stt = stt + take_eta(z_new) * m
                return (nd, stt), z_new

            (nd, stt), z_t = jax.lax.scan(
                token_step, (sub(ndt), sub(st)),
                (tok_t, mask_t, z_t, n_iota), unroll=unroll)
            ndt = ndt.at[r0:].set(nd) if r0 else nd
            st = st.at[r0:].set(stt) if r0 else stt
            new_z.append(z_t)

        if refresh:  # whole-corpus delayed-count refresh (exact scatter)
            for (tok_t, mask_t, r0, _), zo_t, zn_t in zip(segs, z_segs,
                                                          new_z):
                w_f = tok_t.ravel()
                zo_f, zn_f = zo_t.ravel(), zn_t.ravel()
                changed = mask_t.ravel() * (zn_f != zo_f) \
                    .astype(jnp.float32)
                ntw_loc = (ntw_loc.at[w_f, zo_f].add(-changed)
                           .at[w_f, zn_f].add(changed))
            nt_loc = nt_loc + jnp.zeros_like(nt_loc) \
                .at[chain_of_row].add(ndt - ndt_start)
        return (tuple(new_z), ndt, ntw_loc, nt_loc), None

    carry = (z_init, ndt0, ntw_t_stack, nt)
    if n_sweeps > 1:
        carry, _ = jax.lax.scan(
            one_sweep, carry, jnp.arange(n_sweeps - 1, dtype=jnp.int32))
    (z_segs, ndt, _, _), _ = one_sweep(
        carry, jnp.int32(n_sweeps - 1), refresh=False)
    return tuple(z.T for z in z_segs), ndt


def slda_train_sweeps_chains_jnp(tokens, mask, seeds, z0, ndt0, y, inv_len,
                                 ntw_t, nt, eta, *, alpha, beta, rho,
                                 supervised=True, n_sweeps=1, doc_block=8,
                                 unroll=8, product_form=False,
                                 ctr_stride=None, sampler_mode="dense",
                                 sparse_topic_cap=32):
    """Chain-batched jnp twin: all inputs carry a leading chain dim M
    (tokens [M, D, N], ntw_t [M, W, T], nt/eta [M, T], ...).

    Unlike prediction — where the chains fold into the document-row axis
    around ONE stacked table (slda_predict.slda_predict_sweeps_chains_jnp)
    — each training chain's table EVOLVES separately between sweeps, so
    the chain axis folds into the block-vmap axis instead: the twin maps
    `block_fn` over chains × blocks in one jitted op.  Expressed as the
    vmap of the single-chain twin, which makes bit-identity to the
    vmapped path hold BY CONSTRUCTION (same jaxpr) while XLA still sees
    one fused [M·B]-lane program — the restructuring the chain grid buys
    on TPU comes from `slda_train_sweeps_chains_pallas`.
    """
    fn = functools.partial(
        slda_train_sweeps_jnp, alpha=alpha, beta=beta, rho=rho,
        supervised=supervised, n_sweeps=n_sweeps, doc_block=doc_block,
        unroll=unroll, product_form=product_form, ctr_stride=ctr_stride,
        sampler_mode=sampler_mode, sparse_topic_cap=sparse_topic_cap)
    return jax.vmap(fn)(tokens, mask, seeds, z0, ndt0, y, inv_len,
                        ntw_t, nt, eta)
