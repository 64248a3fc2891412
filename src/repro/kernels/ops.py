"""Public jit'd wrappers around the Pallas kernels.

Each op pads its inputs to the kernels' tiling constraints, dispatches to
the kernel (interpret-mode on CPU, compiled on TPU), and exposes a
`use_pallas=False` escape hatch to the pure-jnp oracle in ref.py.  The
model zoo and the sLDA core call ONLY these entry points.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention
from .rmsnorm import rmsnorm as _rmsnorm_kernel
from .slda_gibbs import slda_gibbs_sweep_pallas
from .slda_predict import (slda_predict_sweeps_chains_jnp,
                           slda_predict_sweeps_chains_pallas,
                           slda_predict_sweeps_jnp,
                           slda_predict_sweeps_pallas)
from .slda_train import (slda_train_sweeps_chains_jnp,
                         slda_train_sweeps_chains_pallas,
                         slda_train_sweeps_jnp,
                         slda_train_sweeps_pallas)
from .sparse import (build_topic_index,  # noqa: F401 (re-export)
                     sparse_two_stage_draw)
from .ssd_scan import ssd_scan, ssd_decode_step  # noqa: F401 (re-export)


def _interpret() -> bool:
    # the ONE platform predicate (shared with SLDAConfig.resolve_backend
    # and the launch runner's auto_pallas flip)
    from repro.core.types import devices_support_pallas
    return not devices_support_pallas()


# §Perf trace-time switches (set by the launcher before lowering; the
# baseline lowering keeps all of them off — see EXPERIMENTS.md §Perf)
OPT = {
    "causal_skip": False,     # triangular-scan causal attention (~2× flops)
    "block_q": 0,             # 0 = default (512); S = no scan → attention
                              # backward psums dK/dV once per layer instead
                              # of once per q block
    "head_shard_axes": None,  # (chain_spec, dp_spec): constrain q/k/v to
                              # HEAD-aligned model sharding — prevents
                              # GSPMD from sharding head_dim (which turns
                              # every attention einsum into a partial-sum
                              # all-reduce of logits-sized tensors)
    "probs_bf16": False,      # store attention probabilities in bf16
                              # (softmax stats stay f32) — halves the
                              # dominant [bq, S] intermediate traffic
    "moe_ep_axes": None,      # chain_spec: constrain the MoE dispatch
                              # buffers to P(chain, 'model', ...) — forces
                              # true expert parallelism instead of letting
                              # GSPMD replicate the buffers (cross-pod!)
}


# ------------------------------------------------------------- slda gibbs

def slda_gibbs_sweep(tokens, mask, uniforms, z, ndt, y, inv_len, ntw, nt,
                     eta, *, alpha, beta, rho, supervised=True,
                     doc_block=8, use_pallas=True, chain_axis=False,
                     sampler_mode="dense", sparse_topic_cap=32):
    """Document-parallel sLDA Gibbs sweep. ntw: [T, W] (un-transposed —
    the row-gather [W, T] layout is an internal kernel detail).

    chain_axis=True runs M independent chains in one call: every array
    gains a leading chain dim (tokens [M, D, N], ntw [M, T, W], nt/eta
    [M, T], ...).  Per-chain results are bit-identical to the unbatched
    call — the jnp route vmaps the per-document oracle over chains and
    the pallas route batches the kernel's grid (tests assert both
    against the nested-vmap core sweep exactly)."""
    if chain_axis:
        fn = functools.partial(
            slda_gibbs_sweep, alpha=alpha, beta=beta, rho=rho,
            supervised=supervised, doc_block=doc_block,
            use_pallas=use_pallas, sampler_mode=sampler_mode,
            sparse_topic_cap=sparse_topic_cap)
        return jax.vmap(fn)(tokens, mask, uniforms, z, ndt, y, inv_len,
                            ntw, nt, eta)
    ntw_t = ntw.T
    if not use_pallas:
        z2, ndt2 = ref.ref_slda_gibbs_sweep(
            tokens, mask, uniforms, z, ndt, y, inv_len, ntw_t, nt, eta,
            alpha, beta, rho, supervised, sampler_mode=sampler_mode,
            sparse_topic_cap=sparse_topic_cap)
        return z2, ndt2
    D = tokens.shape[0]
    pad = (-D) % doc_block
    if pad:
        pad2 = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        tokens, mask, uniforms, z, ndt, y, inv_len = map(
            pad2, (tokens, mask, uniforms, z, ndt, y, inv_len))
    z2, ndt2 = slda_gibbs_sweep_pallas(
        tokens, mask, uniforms, z, ndt, y, inv_len, ntw_t, nt, eta,
        alpha=alpha, beta=beta, rho=rho, supervised=supervised,
        doc_block=doc_block, interpret=_interpret(),
        sampler_mode=sampler_mode, sparse_topic_cap=sparse_topic_cap)
    if pad:
        z2, ndt2 = z2[:D], ndt2[:D]
    return z2, ndt2


# ------------------------------------------------------------- slda train

def slda_train_sweeps(tokens, mask, z0, ndt0, y, inv_len, ntw, nt, eta,
                      seeds, *, alpha, beta, rho, n_sweeps, supervised=True,
                      doc_block=8, use_pallas=True, tpu_prng=False,
                      unroll=8, product_form=False, chain_axis=False,
                      ctr_stride=None, sampler_mode="dense",
                      sparse_topic_cap=32):
    """`n_sweeps` training Gibbs sweeps in one fused launch per doc block.

    ntw: [T, W] (un-transposed — the row-gather [W, T] layout is an
    internal kernel detail); seeds: int32 [D] per-document PRNG seeds.
    Returns (z_final [D, N], ndt_final [D, T]).  The topic-word table
    refreshes *block-locally* between the launch's sweeps (delayed counts
    across blocks, DESIGN.md §Train-kernel) — the caller refreshes the
    exact global tables afterwards (the plan recounts them from
    z_final).  At n_sweeps=1 the launch is exactly
    one seed-semantics sweep (keep product_form=False there to preserve
    the seed sampling bits).

    product_form=True samples the categorical from the plain product of
    positives times one Gaussian `exp` instead of three `log`s — same
    distribution, cheaper transcendentals; the multi-sweep fused chain
    path enables it via `SLDAConfig.product_form_sweeps` (see
    slda_train.py).  Kernel, twin and oracle share either form
    bit-for-bit.

    chain_axis=True runs M independent chains in ONE launch — the
    chain-batched form (DESIGN.md §Chain-batched): every array gains a
    leading chain dim (tokens [M, D, N], ntw [M, T, W], nt/eta [M, T],
    seeds [M, D], ...), the pallas route becomes one grid-(M, B) kernel
    launch, and each chain's result is bit-identical to its unbatched
    call.

    use_pallas=False routes to the blocked-jnp fast path, bit-identical
    to the interpret-mode kernel (shared counter-hash PRNG + op order).
    The doc_block is part of the *semantics* here (it sets the delayed-
    count granularity), so both routes pad D to a doc_block multiple and
    share the same block partition.

    ctr_stride pins the per-sweep PRNG counter stride (default: the
    padded token width N).  The length-bucketed execution layer
    (DESIGN.md §Ragged-execution) passes the SOURCE corpus max_len here
    while looping only each bucket's smaller width, so every (doc,
    sweep, token) triple draws the same uniform as the unbucketed launch.
    """
    d_axis = 1 if chain_axis else 0
    ntw_t = jnp.swapaxes(ntw, -1, -2)
    D = tokens.shape[d_axis]
    pad = (-D) % doc_block
    if pad:
        pad2 = lambda a: jnp.pad(
            a, ((0, 0),) * d_axis + ((0, pad),)
            + ((0, 0),) * (a.ndim - 1 - d_axis))
        tokens, mask, z0, ndt0, y, inv_len, seeds = map(
            pad2, (tokens, mask, z0, ndt0, y, inv_len, seeds))
    kw = dict(alpha=alpha, beta=beta, rho=rho, supervised=supervised,
              n_sweeps=n_sweeps, doc_block=doc_block,
              product_form=product_form, ctr_stride=ctr_stride,
              sampler_mode=sampler_mode, sparse_topic_cap=sparse_topic_cap)
    if use_pallas:
        fn = (slda_train_sweeps_chains_pallas if chain_axis
              else slda_train_sweeps_pallas)
        z2, ndt2 = fn(tokens, mask, seeds, z0, ndt0, y, inv_len, ntw_t,
                      nt, eta, interpret=_interpret(), tpu_prng=tpu_prng,
                      **kw)
    else:
        fn = (slda_train_sweeps_chains_jnp if chain_axis
              else slda_train_sweeps_jnp)
        z2, ndt2 = fn(tokens, mask, seeds, z0, ndt0, y, inv_len, ntw_t,
                      nt, eta, unroll=unroll, **kw)
    if pad:
        sl = (slice(None),) * d_axis + (slice(None, D),)
        z2, ndt2 = z2[sl], ndt2[sl]
    return z2, ndt2


# ----------------------------------------------------------- slda predict

def slda_predict_sweeps(tokens, mask, z0, ndt0, phi, seeds, *, alpha,
                        n_burnin, n_samples, doc_block=8, use_pallas=True,
                        tpu_prng=False, chain_axis=False, ctr_stride=None,
                        sampler_mode="dense", sparse_topic_cap=32):
    """All `n_burnin + n_samples` test-time Gibbs sweeps in one fused pass.

    phi: [T, W] (un-transposed — the row-gather [W, T] layout is an
    internal kernel detail); seeds: int32 [D] per-document PRNG seeds.
    Returns (ndt_avg [D, T], z_final [D, N]).

    chain_axis=True is the chain-batched form (DESIGN.md §Chain-batched):
    phi [M, T, W], seeds [M, D], z0 [M, D, N], ndt0 [M, D, T], while
    tokens/mask may stay [D, N] — the corpus every chain predicts is
    SHARED, so the pallas route reads one token tile per doc block for
    all M chains (grid (M, B)) and the jnp route folds the chains into
    the document-row axis around one stacked [M·W, T] table.  Per-chain
    results are bit-identical to the unbatched call; returns
    (ndt_avg [M, D, T], z_final [M, D, N]).

    use_pallas=False routes to the batched-jnp fast path, which is
    bit-identical to the interpret-mode kernel (shared counter-hash PRNG
    and op order).  tpu_prng=True uses the native TPU PRNG inside the
    compiled kernel (faster on hardware; one stream per doc block, so the
    per-document seeds are honored only by the hash path, and results are
    not reproducible against it).

    ctr_stride pins the per-sweep PRNG counter stride (default: the
    padded token width N); the length-bucketed execution layer passes
    the source corpus max_len (DESIGN.md §Ragged-execution).
    """
    phi_t = jnp.swapaxes(phi, -1, -2)
    kw = dict(alpha=alpha, n_burnin=n_burnin, n_samples=n_samples,
              ctr_stride=ctr_stride, sampler_mode=sampler_mode,
              sparse_topic_cap=sparse_topic_cap)
    if not use_pallas:
        fn = (slda_predict_sweeps_chains_jnp if chain_axis
              else slda_predict_sweeps_jnp)
        return fn(tokens, mask, seeds, z0, ndt0, phi_t, **kw)
    d_axis = 1 if chain_axis else 0
    D = z0.shape[d_axis]
    pad = (-D) % doc_block
    if pad:
        padk = lambda k: lambda a: jnp.pad(
            a, ((0, 0),) * k + ((0, pad),) + ((0, 0),) * (a.ndim - 1 - k))
        tokens, mask = map(padk(tokens.ndim - 2), (tokens, mask))
        z0, ndt0, seeds = map(padk(d_axis), (z0, ndt0, seeds))
    if chain_axis:
        if tokens.ndim == 3:   # per-chain corpora: fall back to batching
            fn = functools.partial(
                slda_predict_sweeps_pallas, doc_block=doc_block,
                interpret=_interpret(), tpu_prng=tpu_prng, **kw)
            ndt_avg, z_final = jax.vmap(fn)(tokens, mask, seeds, z0, ndt0,
                                            phi_t)
        else:
            ndt_avg, z_final = slda_predict_sweeps_chains_pallas(
                tokens, mask, seeds, z0, ndt0, phi_t, doc_block=doc_block,
                interpret=_interpret(), tpu_prng=tpu_prng, **kw)
    else:
        ndt_avg, z_final = slda_predict_sweeps_pallas(
            tokens, mask, seeds, z0, ndt0, phi_t, doc_block=doc_block,
            interpret=_interpret(), tpu_prng=tpu_prng, **kw)
    if pad:
        sl = (slice(None),) * d_axis + (slice(None, D),)
        ndt_avg, z_final = ndt_avg[sl], z_final[sl]
    return ndt_avg, z_final


# -------------------------------------------------------------- attention

def attention_blocked_jnp(q, k, v, *, causal=True, scale=None, kv_len=None,
                          block_q=512):
    """Memory-bounded pure-jnp attention: lax.scan over q blocks, full-S
    logits per block.  Same math as the flash kernel but expressed as plain
    einsums, so XLA's SPMD partitioner can shard it (batch / heads) — this
    is the distributed lowering path; the Pallas kernel is the on-chip TPU
    hot path (see DESIGN.md §6)."""
    B, Hq, Sq, Dh = q.shape
    _, Hkv, Sk, _ = k.shape
    g = Hq // Hkv
    if scale is None:
        scale = Dh ** -0.5
    bq = min(block_q, Sq)
    pad = (-Sq) % bq
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else q
    nb = qp.shape[2] // bq
    qb = jnp.moveaxis(qp.reshape(B, Hkv, g, nb, bq, Dh), 3, 0)  # [nb,B,Hkv,g,bq,Dh]
    kg = k.reshape(B, Hkv, Sk, Dh)
    vg = v.reshape(B, Hkv, Sk, Dh)
    ks_idx = jnp.arange(Sk)
    valid = (ks_idx[None, :] < kv_len[:, None]) if kv_len is not None else None

    def blk(carry, inp):
        qi, qblk = inp
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qblk.astype(jnp.float32),
                       kg.astype(jnp.float32)) * scale
        rows = qi * bq + jnp.arange(bq) + (Sk - Sq)
        mask = jnp.ones((bq, Sk), bool)
        if causal:
            mask &= ks_idx[None, :] <= rows[:, None]
        if valid is not None:
            mask = mask[None] & valid[:, None, :]
            mask = mask[:, None, None]
        else:
            mask = mask[None, None, None]
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        if OPT["probs_bf16"]:
            o = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(jnp.bfloat16),
                           vg.astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32)
        else:
            o = jnp.einsum("bhgqk,bhkd->bhgqd", p, vg.astype(jnp.float32))
        return carry, o.astype(q.dtype)

    _, ob = jax.lax.scan(blk, 0, (jnp.arange(nb), qb))
    out = jnp.moveaxis(ob, 0, 3).reshape(B, Hq, Sq + pad, Dh)
    return out[:, :, :Sq] if pad else out


def attention_triangular_jnp(q, k, v, *, scale=None, block=512,
                             probs_dtype=jnp.bfloat16):
    """Causal attention as a scan over the LOWER-TRIANGULAR (i, j≤i) block
    pairs with online softmax — ~2× fewer FLOPs/bytes than the full-square
    blocked path (the static-shape analogue of the Pallas kernel's
    `pl.when` causal skip).  Probabilities are stored in `probs_dtype`
    (softmax stats stay f32) — halves the dominant [bq, S] intermediate
    traffic.  §Perf optimization; ops.attention(opt_causal=True) selects it.
    """
    B, Hq, S, Dh = q.shape
    Hkv = k.shape[1]
    g = Hq // Hkv
    if scale is None:
        scale = Dh ** -0.5
    bq = min(block, S)
    pad = (-S) % bq
    if pad:
        qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    else:
        qp, kp, vp = q, k, v
    Sp = qp.shape[2]
    nb = Sp // bq
    q5 = qp.reshape(B, Hkv, g, Sp, Dh)

    # lower-triangular pair list, i-major so each i's stats stream in order
    pairs = [(i, j) for i in range(nb) for j in range(i + 1)]
    ii = jnp.asarray([p[0] for p in pairs], jnp.int32)
    jj = jnp.asarray([p[1] for p in pairs], jnp.int32)
    tri = (jnp.arange(bq)[None, :] <= jnp.arange(bq)[:, None])

    def step(carry, ij):
        out, acc, m, l = carry
        i, j = ij
        qb = jax.lax.dynamic_slice_in_dim(q5, i * bq, bq, 3)  # [B,Hkv,g,bq,Dh]
        kb = jax.lax.dynamic_slice_in_dim(kp, j * bq, bq, 2)  # [B,Hkv,bq,Dh]
        vb = jax.lax.dynamic_slice_in_dim(vp, j * bq, bq, 2)

        fresh = (j == 0)
        m0 = jnp.where(fresh, jnp.full_like(m, -1e30), m)
        l0 = jnp.where(fresh, jnp.zeros_like(l), l)
        a0 = jnp.where(fresh, jnp.zeros_like(acc), acc)

        s = jnp.einsum("bhgqd,bhkd->bhgqk", qb.astype(jnp.float32),
                       kb.astype(jnp.float32)) * scale
        s = jnp.where((i == j) & ~tri, -1e30, s)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m0, m_cur)
        p = jnp.exp(s - m_new).astype(probs_dtype)
        corr = jnp.exp(m0 - m_new)
        l_new = l0 * corr + jnp.sum(p.astype(jnp.float32), -1, keepdims=True)
        a_new = a0 * corr + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p, vb.astype(probs_dtype)).astype(jnp.float32)

        done = (i == j)             # last j for this i → publish block i
        blk = (a_new / jnp.maximum(l_new, 1e-30)).astype(out.dtype)
        # O(block) conditional write: re-write the current content when not
        # done, so traffic stays per-block (XLA updates the carry in place)
        cur = jax.lax.dynamic_slice_in_dim(out, i * bq, bq, 3)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(done, blk, cur), i * bq, 3)
        return (out, a_new, m_new, l_new), None

    out0 = jnp.zeros_like(q5)
    acc0 = jnp.zeros((B, Hkv, g, bq, Dh), jnp.float32)
    m0 = jnp.full((B, Hkv, g, bq, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Hkv, g, bq, 1), jnp.float32)
    (out, _, _, _), _ = jax.lax.scan(step, (out0, acc0, m0, l0), (ii, jj))
    out = out.reshape(B, Hq, Sp, Dh)
    return out[:, :, :S] if pad else out


def attention(q, k, v, *, causal=True, scale=None, kv_len=None,
              block_q=128, block_k=128, use_pallas=True, opt_causal=False):
    """Flash attention with GQA.  q: [B,Hq,Sq,Dh]; k/v: [B,Hkv,Sk,Dh].

    use_pallas=False routes to the partitionable blocked-jnp paths (decode
    with Sq == 1 short-circuits to the plain einsum oracle);
    opt_causal=True selects the triangular-scan §Perf variant."""
    if not use_pallas:
        if q.shape[2] == 1:
            return ref.ref_attention(q, k, v, causal=causal, scale=scale,
                                     kv_len=kv_len)
        if ((opt_causal or OPT["causal_skip"]) and causal and kv_len is None
                and q.shape[2] == k.shape[2]):
            return attention_triangular_jnp(q, k, v, scale=scale)
        return attention_blocked_jnp(q, k, v, causal=causal, scale=scale,
                                     kv_len=kv_len,
                                     block_q=OPT["block_q"] or 512)
    B, Hq, Sq, Dh = q.shape
    Sk = k.shape[2]
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    pq, pk = (-Sq) % bq, (-Sk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
        if kv_len is None:
            kv_len = jnp.full((B,), Sk, jnp.int32)   # mask the padded tail
    out = flash_attention(q, k, v, causal=causal, scale=scale, kv_len=kv_len,
                          block_q=bq, block_k=bk, interpret=_interpret())
    return out[:, :, :Sq] if pq else out


# -------------------------------------------------------------------- ssd

def ssd_chunked_jnp(x, dt, A, B, C, *, chunk=64):
    """Chunked SSD as plain einsums + a scan over chunks — the SPMD-
    partitionable twin of the Pallas kernel (identical chunk algebra)."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    L = chunk
    nc = x.shape[1] // L
    # [nc, b, L, ...] chunk-major for the scan
    xc = jnp.moveaxis(x.reshape(b, nc, L, h, p), 1, 0).astype(jnp.float32)
    dtc = jnp.moveaxis(dt.reshape(b, nc, L, h), 1, 0).astype(jnp.float32)
    Bc = jnp.moveaxis(B.reshape(b, nc, L, n), 1, 0).astype(jnp.float32)
    Cc = jnp.moveaxis(C.reshape(b, nc, L, n), 1, 0).astype(jnp.float32)
    tri = (jnp.arange(L)[None, :] <= jnp.arange(L)[:, None])

    def step(state, inp):
        xk, dk, bk, ck = inp                     # [b,L,h,p],[b,L,h],[b,L,n]
        a = A[None, None, :] * dk                # [b, L, h]
        cum = jnp.cumsum(a, axis=1)
        G = jnp.einsum("bln,bmn->blm", ck, bk)   # [b, L, L]
        Mdec = jnp.exp(cum[:, :, None, :] - cum[:, None, :, :])   # [b,L,L,h]
        M = jnp.where(tri[None, :, :, None], Mdec, 0.0) * dk[:, None]
        y = jnp.einsum("blm,blmh,bmhp->blhp", G, M, xk)
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "bln,bhpn->blhp", ck, state)
        w = jnp.exp(cum[:, -1:, :] - cum) * dk   # [b, L, h]
        state = state * jnp.exp(cum[:, -1])[:, :, None, None] + jnp.einsum(
            "blhp,blh,bln->bhpn", xk, w, bk)
        return state, y

    init = jnp.zeros((b, h, p, n), jnp.float32)
    _, yc = jax.lax.scan(step, init, (xc, dtc, Bc, Cc))
    y = jnp.moveaxis(yc, 0, 1).reshape(b, nc * L, h, p).astype(x.dtype)
    return y[:, :s] if pad else y


def ssd(x, dt, A, B, C, *, chunk=64, use_pallas=True):
    """Mamba-2 SSD scan.  x: [b,s,h,p]; dt: [b,s,h]; A: [h]; B/C: [b,s,n].

    use_pallas=False routes to the partitionable chunked-jnp path."""
    if not use_pallas:
        return ssd_chunked_jnp(x, dt, A, B, C, chunk=chunk)
    s = x.shape[1]
    ch = min(chunk, s)
    pad = (-s) % ch
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    y = ssd_scan(x, dt, A, B, C, chunk=ch, interpret=_interpret())
    return y[:, :s] if pad else y


# ---------------------------------------------------------------- rmsnorm

def rmsnorm(x, w, *, eps=1e-6, use_pallas=True):
    if not use_pallas:
        return ref.ref_rmsnorm(x, w, eps)
    return _rmsnorm_kernel(x, w, eps=eps, interpret=_interpret())
