"""Pallas TPU kernel for the sLDA *prediction* sweeps — the true hot path.

The paper's slowest variant (Weighted Average, Section III-C(d)) spends its
time predicting: every chain must run `n_pred_burnin + n_pred_samples`
test-time Gibbs sweeps over BOTH the test set and the full training set.
The training kernel (slda_gibbs.py) launches once per sweep because the
topic-word table must be refreshed globally between sweeps; prediction has
no such barrier — φ̂ is frozen — so ALL sweeps for a document block fuse
into ONE kernel launch here (DESIGN.md §Predict-kernel).

Three things make the fused kernel cheap:

  * layout — φ̂ is stored transposed, ``phi_t [W, T]``, resident in VMEM;
    per token each document loads its word's row, addressed from an SMEM
    copy of the block's word ids (`access.gather_rows`, DESIGN.md
    §Predict-kernel "Row access on the chip" — the same access as the
    train kernel's ``ntw_t``);
  * no log/exp — prediction is unsupervised, p(z=t) ∝ (N_dt^{-dn}+α)·φ̂_tw,
    a product of positives, so the categorical is sampled from the plain
    product instead of a log-sum-exp (the Gaussian response term that
    forces the train kernel into log space does not appear at test time);
  * matmul prefix-sum — the inverse-CDF's cumulative sum is computed as
    ``p @ U`` with U upper-triangular ones: one [DB, T]·[T, T] contraction
    that lands on the MXU on TPU and on a single gemm call on XLA:CPU,
    instead of a fusion-breaking `cumsum` + reduce pair per token (the
    single biggest CPU win — the token loop is dispatch-bound, not
    FLOP-bound);
  * counter-based PRNG — per-token uniforms are derived in-kernel from a
    murmur3-style mix of (doc_seed, sweep·N + n).  The seed path
    pre-materialized a ``[D, n_sweeps, N]`` uniform tensor, a multi-GB
    allocation at the paper's corpus sizes (it OOMed the Fig. 6 run).  On
    real TPU hardware ``tpu_prng=True`` swaps in the native
    ``pltpu.prng_random_bits`` generator — one hardware stream per doc
    block, seeded from a murmur mix of the block's first per-document
    seed and the grid index, so the per-DOCUMENT seed contract holds only
    on the portable hash path (off by default; also not bit-reproducible
    against the hash).

Post-burn-in ``ndt`` averages are accumulated in-kernel, so the only
outputs are ``ndt_avg [D, T]`` and the final assignments ``z [D, N]``.

Grid: (D / doc_block,).  `ref.ref_slda_predict_sweeps` is the oracle;
`slda_predict_sweeps_jnp` below is the bit-identical batched-jnp CPU fast
path (the one the benchmarks measure on this container).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.mathutil import upper_tri_ones
from .access import check_compiled_mode, column, gather_rows, set_column
from .sparse import (build_topic_index, gather_index_rows,
                     sparse_two_stage_draw)

from jax.experimental.pallas import tpu as pltpu

# murmur3 finalizer constants (public domain, Austin Appleby)
_MIX1 = np.uint32(0x85EBCA6B)
_MIX2 = np.uint32(0xC2B2AE35)
_GOLDEN = np.uint32(0x9E3779B9)
_INV24 = np.float32(2.0 ** -24)

# φ̂-gather hoist gate of the jnp twin (bitwise-neutral, perf only).
# Chain folding multiplied the twin's document-row counts by M (PR 3), so
# the [N, D_rows, T] hoisted tensor now crosses the CPU cache budget long
# before the old 64 MB cap.  Interleaved A/B on this container (T=8,
# W=1000, 25 sweeps; hoist-on vs hoist-off as distinct jitted callables):
#   rows=64..256, N=64 (0.12-0.5 MB): hoist 1.23-1.27x FASTER
#   rows=512, N=64 (1 MB):            0.96x — break-even/loss
#   rows=1024..4096, N=128..256 (4-32 MB): 0.82-0.92x — clear loss
# so the win collapses right around ~1 MB: re-gathering φ̂ rows per sweep
# beats streaming a cache-busting tensor 25 times.  512 KB keeps the
# small single-chain shapes that motivated the hoist (PR 1) inside the
# gate and pushes every M-folded paper-scale shape out.
_HOIST_T_MAX = 16
_HOIST_BYTES_MAX = 512 * 2 ** 10


def counter_uniform(seed, ctr):
    """Counter-based uniform in [0, 1): murmur3-finalizer mix of (seed, ctr).

    Pure elementwise integer ops — identical results inside a Pallas kernel
    (interpret or compiled), under jit, and in plain numpy-style jnp, which
    is what lets the kernel, the batched-jnp fast path, and the ref oracle
    share uniforms bit-for-bit.  Broadcasts over both arguments.
    """
    x = jnp.asarray(seed).astype(jnp.uint32) ^ (
        jnp.asarray(ctr).astype(jnp.uint32) * _GOLDEN)
    x = (x ^ (x >> 16)) * _MIX1
    x = (x ^ (x >> 13)) * _MIX2
    x = x ^ (x >> 16)
    return bits_to_uniform(x)


def bits_to_uniform(x):
    """Top 24 of 32 random bits → f32 in [0, 1), strictly < 1 so the
    inverse CDF stays in range.  Goes through int32 (exact: x >> 8 <
    2^24), because the TPU compiler has no uint32 → float32 cast."""
    return (x >> 8).astype(jnp.int32).astype(jnp.float32) * _INV24


def predict_uniforms(seeds, n_sweeps: int, n_tokens: int,
                     ctr_stride: int | None = None):
    """Materialize the full [D, n_sweeps, N] uniform tensor the kernel
    derives on the fly — for feeding the ref oracle in equivalence tests.
    (Never used in production: this allocation is exactly what the fused
    kernel exists to avoid.)

    ctr_stride is the per-sweep counter stride (default: n_tokens).  The
    length-bucketed execution layer keeps it pinned to the SOURCE corpus
    max_len while looping only a bucket's (smaller) padded width, so every
    (doc, sweep, token) triple draws the same uniform it would in the
    unbucketed launch (DESIGN.md §Ragged-execution)."""
    if ctr_stride is None:
        ctr_stride = n_tokens
    ctr = (jnp.arange(n_sweeps, dtype=jnp.int32)[:, None] * ctr_stride
           + jnp.arange(n_tokens, dtype=jnp.int32)[None, :])
    return counter_uniform(seeds[:, None, None], ctr[None])


def _predict_kernel(tokens_ref, mask_ref, seed_ref, z_ref, ndt_ref, phi_t_ref,
                    *refs, alpha: float, n_burnin: int, n_samples: int,
                    n_tokens: int, ctr_stride: int, tpu_prng: bool,
                    chain_grid: bool = False, sampler_mode: str = "dense"):
    # tokens_ref holds the doc block's word ids in SMEM (row addresses
    # for gather_rows); phi_t_ref is the [W, T] table in VMEM.  Sparse
    # mode appends the three per-word topic-index inputs (frozen like φ̂
    # itself); it runs interpreted only (access.check_compiled_mode)
    if sampler_mode == "sparse":
        idx_ref, vmask_ref, occm_ref, z_out_ref, avg_ref, rows_ref = refs
    else:
        z_out_ref, avg_ref, rows_ref = refs
    seeds = seed_ref[:, 0]                    # [DB]
    T = phi_t_ref.shape[1]
    topic_iota = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    tri_u = upper_tri_ones(T)
    mask = mask_ref[...]                      # [DB, N]

    if tpu_prng:
        # one hardware stream per DOC BLOCK (the per-core PRNG is stateful,
        # so per-document seeds cannot be honored here — only the portable
        # hash path keeps that contract).  Mix the block's first seed with
        # the (flattened) grid position through the murmur finalizer so
        # that distinct blocks get structurally uncorrelated streams (a
        # plain `seed + program_id` collides whenever s_i + i == s_j + j).
        pid = pl.program_id(0)
        if chain_grid:
            pid = pid * pl.num_programs(1) + pl.program_id(1)
        mixed = seed_ref[0, 0].astype(jnp.uint32) ^ (
            pid.astype(jnp.uint32) * _GOLDEN)
        mixed = (mixed ^ (mixed >> 16)) * _MIX1
        mixed = (mixed ^ (mixed >> 13)) * _MIX2
        pltpu.prng_seed((mixed ^ (mixed >> 16)).astype(jnp.int32))

    z_out_ref[...] = z_ref[...]               # z persists across sweeps here
    ndt0 = ndt_ref[...]                       # [DB, T]

    def sweep_body(s, carry):
        ndt, acc = carry

        def token_step(n, ndt):
            m = column(mask, n)               # [DB]
            z_old = column(z_out_ref[...], n)  # [DB]
            if tpu_prng:
                u = bits_to_uniform(pltpu.bitcast(
                    pltpu.prng_random_bits((m.shape[0], 1)),
                    jnp.uint32))[:, 0]
            else:
                u = counter_uniform(seeds, s * ctr_stride + n)

            old = (topic_iota == z_old[:, None]).astype(jnp.float32) * m[:, None]
            ndt = ndt - old
            p = (ndt + alpha) * gather_rows(phi_t_ref, tokens_ref, n,
                                            rows_ref)
            if sampler_mode == "sparse":
                # two-stage sparse draw (rare stage-2 correction
                # predicated inside — kernels/sparse.py)
                w = column(tokens_ref[...], n)
                z_new = sparse_two_stage_draw(
                    p, u, *gather_index_rows(w, idx_ref[...],
                                             vmask_ref[...], occm_ref[...]))
            else:
                c = jnp.dot(p, tri_u)                           # prefix sums
                z_new = jnp.sum(
                    (c < (u * c[:, T - 1])[:, None]).astype(jnp.int32), axis=1)
            z_new = jnp.where(m > 0, z_new, z_old).astype(jnp.int32)
            ndt = ndt + (topic_iota == z_new[:, None]).astype(jnp.float32) \
                * m[:, None]
            set_column(z_out_ref, n, z_new)
            return ndt

        ndt = jax.lax.fori_loop(0, n_tokens, token_step, ndt)
        keep = (s >= n_burnin).astype(jnp.float32)
        return ndt, acc + keep * ndt

    _, acc = jax.lax.fori_loop(0, n_burnin + n_samples, sweep_body,
                               (ndt0, jnp.zeros_like(ndt0)))
    # explicit f32 reciprocal multiply: a literal `acc / n` is rewritten to
    # divide-or-reciprocal at XLA's whim, which costs 1 ulp of cross-path
    # reproducibility when n is not a power of two
    avg_ref[...] = acc * np.float32(1.0 / n_samples)


def slda_predict_sweeps_pallas(tokens, mask, seeds, z0, ndt0, phi_t, *,
                               alpha, n_burnin, n_samples, doc_block=8,
                               interpret=True, tpu_prng=False,
                               ctr_stride=None, sampler_mode="dense",
                               sparse_topic_cap=32, topic_index=None):
    """All prediction sweeps for every document in ONE launch per doc block.

    tokens/mask/z0: [D, N]; seeds: int32 [D]; ndt0: [D, T]; phi_t: [W, T].
    Returns (ndt_avg [D, T], z_final [D, N]).  D must be a multiple of
    doc_block (ops.py pads).  ctr_stride pins the PRNG counter stride
    (default N — see predict_uniforms).
    """
    check_compiled_mode(sampler_mode, interpret)
    D, N = tokens.shape
    T = ndt0.shape[-1]
    W = phi_t.shape[0]
    assert D % doc_block == 0, (D, doc_block)
    grid = (D // doc_block,)

    doc_spec = lambda cols: pl.BlockSpec((doc_block, cols), lambda i: (i, 0))
    full = lambda shape: pl.BlockSpec(shape, lambda i: tuple(0 for _ in shape))

    kernel = functools.partial(
        _predict_kernel, alpha=float(alpha), n_burnin=int(n_burnin),
        n_samples=int(n_samples), n_tokens=N,
        ctr_stride=int(N if ctr_stride is None else ctr_stride),
        tpu_prng=tpu_prng, sampler_mode=sampler_mode)

    ids_spec = pl.BlockSpec((doc_block, N), lambda i: (i, 0),
                            memory_space=pltpu.SMEM)
    in_specs = [ids_spec, doc_spec(N), doc_spec(1), doc_spec(N),
                doc_spec(T), full((W, T))]
    operands = [tokens, mask, seeds[:, None], z0, ndt0, phi_t]
    if sampler_mode == "sparse":
        if topic_index is None:
            topic_index = build_topic_index(phi_t, sparse_topic_cap)
        cap = topic_index[0].shape[-1]
        in_specs += [full((W, cap)), full((W, cap)), full((W, T))]
        operands += list(topic_index)

    z_final, ndt_avg = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[doc_spec(N), doc_spec(T)],
        out_shape=[jax.ShapeDtypeStruct((D, N), jnp.int32),
                   jax.ShapeDtypeStruct((D, T), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((doc_block, T), jnp.float32)],
        interpret=interpret,
    )(*operands)
    return ndt_avg, z_final


def slda_predict_sweeps_chains_pallas(tokens, mask, seeds, z0, ndt0, phi_t,
                                      *, alpha, n_burnin, n_samples,
                                      doc_block=8, interpret=True,
                                      tpu_prng=False, ctr_stride=None,
                                      sampler_mode="dense",
                                      sparse_topic_cap=32,
                                      topic_index=None):
    """Chain-batched fused prediction: grid (M, D/doc_block), ONE launch
    for all M chains of the paper's parallel algorithms.

    tokens/mask: [D, N] — SHARED across chains: the token/mask BlockSpecs
    ignore the chain grid index, so ONE [D, N] corpus feeds all M chains
    instead of an M-way replicated [M, D, N] copy (the Weighted Average
    work-set is the test set plus the full training set, re-swept once
    per chain — the paper's stated dominant cost).  The chain axis is
    the OUTER grid dim, so each chain's φ̂ block stays resident across
    that chain's doc blocks (the [W, T] table is the large operand; the
    [doc_block, N] token tile is re-fetched per grid step either way).
    Per-chain state rides `None`-squeezed specs: seeds [M, D]; z0
    [M, D, N]; ndt0 [M, D, T]; phi_t [M, W, T].  The kernel body is
    EXACTLY `_predict_kernel`, so each chain's output is bit-identical
    to its single-chain launch.
    Returns (ndt_avg [M, D, T], z_final [M, D, N]).
    """
    check_compiled_mode(sampler_mode, interpret)
    D, N = tokens.shape
    M = phi_t.shape[0]
    T = ndt0.shape[-1]
    W = phi_t.shape[1]
    assert D % doc_block == 0, (D, doc_block)
    grid = (M, D // doc_block)

    shared = lambda cols: pl.BlockSpec((doc_block, cols),
                                       lambda c, i: (i, 0))
    cdoc = lambda cols: pl.BlockSpec((None, doc_block, cols),
                                     lambda c, i: (c, i, 0))
    cfull = lambda shape: pl.BlockSpec(
        (None,) + shape, lambda c, i: (c,) + tuple(0 for _ in shape))

    kernel = functools.partial(
        _predict_kernel, alpha=float(alpha), n_burnin=int(n_burnin),
        n_samples=int(n_samples), n_tokens=N,
        ctr_stride=int(N if ctr_stride is None else ctr_stride),
        tpu_prng=tpu_prng, chain_grid=True, sampler_mode=sampler_mode)

    ids_spec = pl.BlockSpec((doc_block, N), lambda c, i: (i, 0),
                            memory_space=pltpu.SMEM)
    in_specs = [ids_spec, shared(N), cdoc(1), cdoc(N), cdoc(T),
                cfull((W, T))]
    operands = [tokens, mask, seeds[..., None], z0, ndt0, phi_t]
    if sampler_mode == "sparse":
        if topic_index is None:
            topic_index = build_topic_index(phi_t, sparse_topic_cap)
        cap = topic_index[0].shape[-1]
        in_specs += [cfull((W, cap)), cfull((W, cap)), cfull((W, T))]
        operands += list(topic_index)

    z_final, ndt_avg = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[cdoc(N), cdoc(T)],
        out_shape=[jax.ShapeDtypeStruct((M, D, N), jnp.int32),
                   jax.ShapeDtypeStruct((M, D, T), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((doc_block, T), jnp.float32)],
        interpret=interpret,
    )(*operands)
    return ndt_avg, z_final


def slda_predict_sweeps_chains_jnp(tokens, mask, seeds, z0, ndt0, phi_t, *,
                                   alpha, n_burnin, n_samples, unroll=8,
                                   ctr_stride=None, sampler_mode="dense",
                                   sparse_topic_cap=32):
    """Chain-batched jnp twin: FOLD the chain axis into the document-row
    axis around one stacked table.

    Prediction's tables are frozen, so M chains over D documents are the
    same computation as one chain over M·D documents against a stacked
    `[M·W, T]` φ̂ with per-chain token-id offsets `w + c·W` — every
    per-token op becomes one flat [M·D, T] row op (flat row gather, one
    gemm) instead of M vmapped lanes with batched-operand gathers.
    Per-document ops are row-independent (the same property the
    kernel-vs-twin block tests already rely on), so the fold is
    bit-identical to vmapping the single-chain twin over chains
    (asserted in tests/test_chain_batched.py).

    tokens/mask: [D, N] (shared) or [M, D, N]; seeds [M, D]; z0
    [M, D, N]; ndt0 [M, D, T]; phi_t [M, W, T].
    Returns (ndt_avg [M, D, T], z_final [M, D, N]).
    """
    M, W, T = phi_t.shape
    if tokens.ndim == 2:
        D, N = tokens.shape
        off = (jnp.arange(M, dtype=jnp.int32) * W)[:, None, None]
        tok_f = (tokens[None] + off).reshape(M * D, N)
        mask_f = jnp.broadcast_to(mask, (M, D, N)).reshape(M * D, N)
    else:
        _, D, N = tokens.shape
        off = (jnp.arange(M, dtype=jnp.int32) * W)[:, None, None]
        tok_f = (tokens + off).reshape(M * D, N)
        mask_f = mask.reshape(M * D, N)
    ndt_avg, z_final = slda_predict_sweeps_jnp(
        tok_f, mask_f, seeds.reshape(M * D), z0.reshape(M * D, N),
        ndt0.reshape(M * D, T), phi_t.reshape(M * W, T),
        alpha=alpha, n_burnin=n_burnin, n_samples=n_samples, unroll=unroll,
        ctr_stride=ctr_stride, sampler_mode=sampler_mode,
        sparse_topic_cap=sparse_topic_cap)
    return ndt_avg.reshape(M, D, T), z_final.reshape(M, D, N)


def slda_predict_stair_jnp(seg_tokens, seg_mask, seg_z0, seg_row_start,
                           seg_tok_start, seeds, ndt0, phi_t, *, alpha,
                           n_burnin, n_samples, ctr_stride, unroll=8,
                           sampler_mode="dense", sparse_topic_cap=32):
    """STAIRCASE prediction twin — the ragged execution layer's CPU
    executor (DESIGN.md §Ragged-execution).

    Documents are sorted ASCENDING by length, so a length-bucket
    schedule's widths w_1 < … < w_K split the token axis into segments
    [w_{k-1}, w_k) in which the docs still alive are exactly the SUFFIX
    of rows starting at `seg_row_start[k]`.  One sweep walks the
    segments in order, each a lax.scan over that segment's positions on
    only the live rows — the total step count stays w_K = N_max (unlike
    per-bucket launches, which re-run the early positions per bucket and
    inflate Σ_b N_b sequential steps; that inflation is what makes
    per-bucket prediction LOSE on dispatch-bound CPU token loops), while
    the executed row-slots collapse to the staircase ≈ Σ true tokens.

    Per-token ops are row-independent and the counter uniforms use the
    GLOBAL token position (`seg_tok_start[k] + n` at stride ctr_stride),
    so per-document results are bit-identical to the padded twin for any
    schedule — same contract as the per-bucket launches
    (tests/test_ragged.py).

    seg_tokens/seg_mask/seg_z0: per-segment arrays [R_k, L_k] with
    R_k = R - seg_row_start[k] (rows are the flat doc axis — the caller
    folds chains doc-major so doc suffixes stay row suffixes);
    seeds: [R]; ndt0: [R, T]; phi_t: [W, T] (stacked [M·W, T] when
    chains are folded, with token ids pre-offset).
    Returns ndt_avg [R, T] (z is consumed internally; prediction's only
    product is the post-burn-in average).
    """
    R, T = ndt0.shape
    n_sweeps = n_burnin + n_samples
    topic_iota = jnp.arange(T, dtype=jnp.int32)[None, :]
    tri_u = upper_tri_ones(T)
    # φ̂ (possibly chain-stacked) is frozen, so the index is too; stacked
    # rows c·W + w equal the per-chain tables bit-for-bit
    if sampler_mode == "sparse":
        s_idx, s_vm, s_om = build_topic_index(phi_t, sparse_topic_cap)
    segs = []
    for tok, mk, z, r0, n0 in zip(seg_tokens, seg_mask, seg_z0,
                                  seg_row_start, seg_tok_start):
        L = tok.shape[-1]
        n_iota = jnp.arange(n0, n0 + L, dtype=jnp.int32)
        segs.append((tok.T, mk.T, int(r0), n_iota))  # token-major
    z_init = tuple(z.T for z in seg_z0)

    def one_sweep(carry, s):
        z_segs, ndt, acc = carry
        new_z = []
        for (tok_t, mask_t, r0, n_iota), z_t in zip(segs, z_segs):
            sub_seeds = seeds[r0:]

            def token_step(nd, inp):
                w, m, z_old, n = inp
                pw = jnp.take(phi_t, w, axis=0)
                u = counter_uniform(sub_seeds, s * ctr_stride + n)
                old = (topic_iota == z_old[:, None]).astype(jnp.float32) \
                    * m[:, None]
                nd = nd - old
                p = (nd + alpha) * pw
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
                return nd, z_new

            nd, z_t = jax.lax.scan(token_step, ndt[r0:],
                                   (tok_t, mask_t, z_t, n_iota),
                                   unroll=unroll)
            ndt = ndt.at[r0:].set(nd) if r0 else nd
            new_z.append(z_t)
        keep = (s >= n_burnin).astype(jnp.float32)
        return (tuple(new_z), ndt, acc + keep * ndt), None

    (_, _, acc), _ = jax.lax.scan(
        one_sweep, (z_init, ndt0, jnp.zeros_like(ndt0)),
        jnp.arange(n_sweeps, dtype=jnp.int32))
    # f32 reciprocal multiply, matching the fused kernel bit-for-bit
    return acc * np.float32(1.0 / n_samples)


def slda_predict_sweeps_jnp(tokens, mask, seeds, z0, ndt0, phi_t, *,
                            alpha, n_burnin, n_samples, unroll=8,
                            ctr_stride=None, sampler_mode="dense",
                            sparse_topic_cap=32):
    """Batched-jnp twin of the fused kernel — the CPU fast path.

    Same restructuring as the kernel, expressed as XLA-friendly jnp: all D
    documents advance in lockstep (one [D, T] vector op per token instead
    of a vmap of per-document scans), φ̂ is row-gathered from the
    transposed [W, T] layout, prefix sums are the same `p @ U` contraction,
    all sweeps fuse into one `lax.scan` (unrolled ×8: the token loop is
    dispatch-bound on CPU), and the uniforms come from the same counter
    hash — so no [D, S, N] tensor, no per-sweep threefry, no log/exp.
    Bit-identical to the interpret-mode kernel (shared op order + PRNG).

    For small topic counts (T ≤ 16, where the gemm no longer dominates,
    and only while the gathered [N, D, T] tensor stays cache-resident —
    see the _HOIST_* gate constants above, re-tuned for M-folded row
    counts) the φ̂ row gather is additionally hoisted out of the sweep
    loop so the sweeps share it instead of re-gathering every sweep.
    """
    D, N = tokens.shape
    if ctr_stride is None:
        ctr_stride = N
    n_sweeps = n_burnin + n_samples
    T = ndt0.shape[-1]
    topic_iota = jnp.arange(T, dtype=jnp.int32)[None, :]
    tok_t = tokens.T                           # [N, D] token-major for scan
    mask_t = mask.T
    n_iota = jnp.arange(N, dtype=jnp.int32)
    tri_u = upper_tri_ones(T)
    # hoist the sweep-invariant φ̂ gather when the [N, D, T] tensor is small
    # — small in T (where the gemm no longer dominates) AND in absolute
    # bytes, so paper-scale corpora never re-materialize the kind of
    # multi-GB tensor this module exists to avoid
    # sparse mode disables the hoist (the index gathers are per-token
    # anyway) and builds the frozen per-word index once per call
    hoist = (sampler_mode != "sparse" and T <= _HOIST_T_MAX
             and N * D * T * 4 <= _HOIST_BYTES_MAX)
    phi_w = jnp.take(phi_t, tok_t, axis=0) if hoist else None
    if sampler_mode == "sparse":
        s_idx, s_vm, s_om = build_topic_index(phi_t, sparse_topic_cap)

    def one_sweep(carry, s):
        z_t, ndt, acc = carry                  # [N, D], [D, T], [D, T]

        def token_step(ndt, inp):
            pw_or_w, m, z_old, n = inp         # [D(,T)], [D], [D], scalar
            pw = pw_or_w if hoist else jnp.take(phi_t, pw_or_w, axis=0)
            u = counter_uniform(seeds, s * ctr_stride + n)
            old = (topic_iota == z_old[:, None]).astype(jnp.float32) * m[:, None]
            ndt = ndt - old
            p = (ndt + alpha) * pw
            if sampler_mode == "sparse":
                z_new = sparse_two_stage_draw(
                    p, u, jnp.take(s_idx, pw_or_w, axis=0),
                    jnp.take(s_vm, pw_or_w, axis=0),
                    jnp.take(s_om, pw_or_w, axis=0))
            else:
                c = jnp.dot(p, tri_u)          # prefix sums on one gemm
                z_new = jnp.sum(
                    (c < (u * c[:, -1])[:, None]).astype(jnp.int32), axis=1)
            z_new = jnp.where(m > 0, z_new, z_old).astype(jnp.int32)
            ndt = ndt + (topic_iota == z_new[:, None]).astype(jnp.float32) \
                * m[:, None]
            return ndt, z_new

        xs = (phi_w if hoist else tok_t, mask_t, z_t, n_iota)
        ndt, z_t = jax.lax.scan(token_step, ndt, xs, unroll=unroll)
        keep = (s >= n_burnin).astype(jnp.float32)
        return (z_t, ndt, acc + keep * ndt), None

    (z_t, _, acc), _ = jax.lax.scan(
        one_sweep, (z0.T, ndt0, jnp.zeros_like(ndt0)),
        jnp.arange(n_sweeps, dtype=jnp.int32))
    # f32 reciprocal multiply, matching the kernel bit-for-bit
    return acc * np.float32(1.0 / n_samples), z_t.T
