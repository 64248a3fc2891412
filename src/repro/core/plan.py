"""The unified execution-plan layer: ONE dispatch path for
(padded | bucketed) × (single | chain-batched) × (pallas | jnp)
(DESIGN.md §Execution-plan).

The paper's communication-free algorithms are a single stochastic-EM
loop with four combine rules; before this layer the repo implemented
that loop once per (layout, chain-batching, backend, fusing) cell.  An
`ExecutionPlan` separates the *schedule* (data layout, partitioning —
Magnusson et al.; Yan et al., Towards Big Topic Modeling) from the
*sampler*:

  * every corpus is canonicalized to a `BucketedCorpus` — padded
    execution is the DEGENERATE 1-bucket schedule with an identity
    permutation and `ctr_stride = max_len`, so the padded code paths
    stop being special (and the degenerate wrap is shape-only, hence
    traceable under jit, unlike real bucketing);
  * every chain layout is chain-batched — a single chain is M=1
    through the chain_axis kernels (bit-identical to the old
    single-chain path, which is deleted);
  * the plan owns all routing: executor ("blocks" per-bucket fused
    launches on the pallas route and for 1-bucket jnp, "stair" stacked
    twins for multi-bucket jnp), the sweeps-per-launch schedule
    (n_full full launches + one remainder).  The count refresh at
    every EM boundary is one exact recount.

Exactness contract (tests/test_dispatch_matrix.py): at
sweeps_per_launch=1 every cell is bit-identical per document to the
seed-semantics reference (threefry uniforms, η solve every sweep) under
any bucketing/permutation — the `ctr_stride` PRNG pinning of
DESIGN.md §Ragged-execution.  At sweeps_per_launch>1 each cell is its
own member of the fused sampler family (statistically equivalent; the
bucket partition doubles as the delayed-count partition).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .regression import solve_eta
from .types import (BucketedCorpus, Corpus, GibbsState, SLDAConfig,
                    SLDAModel, _stair_segments, _take_docs,
                    _unstair_segments, bucket_corpus, bucket_signature,
                    counts_from_assignments)


# ------------------------------------------------------- canonicalization

def as_bucketed(corpus) -> BucketedCorpus:
    """Canonicalize to the degenerate 1-bucket schedule (identity
    permutation, `ctr_stride = max_len`) — the padded path as a plan
    cell.  Shape-only, so it is traceable under jit; a `BucketedCorpus`
    passes through untouched."""
    if isinstance(corpus, BucketedCorpus):
        return corpus
    d_axis = corpus.tokens.ndim - 2            # 0 flat, 1 chain-sharded
    D = corpus.tokens.shape[d_axis]
    perm = jnp.broadcast_to(jnp.arange(D, dtype=jnp.int32),
                            corpus.tokens.shape[:d_axis] + (D,))
    return BucketedCorpus(buckets=(corpus,), perm=perm, inv_perm=perm,
                          ctr_stride=corpus.tokens.shape[-1],
                          identity=True)


def build_schedule(corpus, cfg: SLDAConfig) -> BucketedCorpus:
    """cfg-driven schedule construction: real length bucketing when
    `cfg.length_buckets > 0` (host-side — needs concrete lengths), the
    degenerate padded wrap otherwise.  Already-bucketed corpora pass
    through, so orchestrators can call this unconditionally."""
    if isinstance(corpus, BucketedCorpus):
        return corpus
    if cfg.length_buckets > 0:
        return bucket_corpus(corpus, cfg.length_buckets,
                             token_block=cfg.bucket_token_block,
                             overhead_docs=cfg.bucket_overhead_docs)
    return as_bucketed(corpus)


def _lift_chain(bc: BucketedCorpus) -> BucketedCorpus:
    """Flat schedule [D, ...] → chain-sharded [1, D, ...] (M=1)."""
    if bc.n_chains is not None:
        return bc
    buckets = tuple(Corpus(tokens=b.tokens[None], mask=b.mask[None],
                           y=b.y[None]) for b in bc.buckets)
    return BucketedCorpus(buckets=buckets, perm=bc.perm[None],
                          inv_perm=bc.inv_perm[None],
                          ctr_stride=bc.ctr_stride, identity=bc.identity)


def _stair_layout(bc: BucketedCorpus, m: int, vocab_size: int):
    """The doc-major chain fold of the STAIRCASE executors — the ONE
    copy of the layout math shared by stair train and stair predict:
    row r = d·M + c (doc suffixes stay row suffixes), per-chain vocab
    offsets into the stacked [M·W, T] table, and per-segment first-row
    / first-token offsets.  Returns
    (fold, unfold, sort, unsort, seg_r0, seg_n0, off)."""
    fold = lambda a: jnp.swapaxes(a, 0, 1).reshape((-1,) + a.shape[2:])
    unfold = lambda a: jnp.swapaxes(a.reshape((-1, m) + a.shape[1:]),
                                    0, 1)
    sort = lambda a: _take_docs(a, bc.perm, 1)
    unsort = lambda a: _take_docs(a, bc.inv_perm, 1)
    starts = np.cumsum([0] + list(bc.counts))
    seg_r0 = [int(s) * m for s in starts[:-1]]
    seg_n0 = [0] + list(bc.widths[:-1])
    off = jnp.arange(m, dtype=jnp.int32) * vocab_size
    return fold, unfold, sort, unsort, seg_r0, seg_n0, off


def build_plan(corpus, cfg: SLDAConfig, backend: str | None = None,
               *, chained: bool = False) -> "ExecutionPlan":
    """Build the plan for `(corpus, cfg, backend)` — all routing happens
    here, once.  `corpus` may be a padded `Corpus` (flat or chain-
    sharded) or a `BucketedCorpus`; it is canonicalized, NOT re-bucketed
    (schedules are data-dependent — build them with `build_schedule`,
    outside jit).  `chained=True` lifts a flat corpus to M=1 so the
    chain-batched loop applies.  `backend=None` resolves from the
    config and the default device (`SLDAConfig.resolve_backend`)."""
    if backend is None:
        backend = cfg.resolve_backend()
    bc = as_bucketed(corpus)
    if chained:
        bc = _lift_chain(bc)
    return ExecutionPlan(corpus=bc, cfg=cfg, backend=backend)


# ----------------------------------------------------------------- plan

@dataclasses.dataclass
class ExecutionPlan:
    """A canonical schedule plus every static routing decision, built
    once from `(corpus, cfg, backend)`.  Registered pytree: the
    schedule arrays are children, `(cfg, backend)` static aux — so a
    plan flows through jit/shard_map and its routing participates in
    the jit cache key."""

    corpus: BucketedCorpus
    cfg: SLDAConfig
    backend: str            # "jnp" | "pallas" | "pallas-interpret"

    def __post_init__(self):
        # the sparse draw has no compiled kernel (kernels/access.py
        # `check_compiled_mode`); refuse the cell here, at plan time
        if self.backend == "pallas" and self.cfg.sampler_mode == "sparse":
            raise NotImplementedError(
                "sampler_mode='sparse' has no compiled TPU kernel; use "
                "sampler_mode='dense' or the jnp route (use_pallas=False)")

    # ---- routing (static)

    @property
    def use_pallas(self) -> bool:
        return self.backend != "jnp"

    @property
    def executor(self) -> str:
        """"blocks": one fused launch per bucket (the pallas route, and
        the degenerate 1-bucket jnp plan == the padded twins).
        "stair": the stacked staircase twins — multi-bucket jnp, where
        per-bucket launches would re-run the token loop per bucket
        (measured loser on CPU; BENCH_slda_ragged.json)."""
        if self.use_pallas or len(self.corpus.buckets) == 1:
            return "blocks"
        return "stair"

    @property
    def n_chains(self):
        return self.corpus.n_chains

    def sweep_schedule(self) -> tuple:
        """(sweeps_per_launch, n_full_launches, remainder_sweeps) —
        total sweeps stay exactly cfg.n_iters."""
        spl = self.cfg.sweeps_per_launch
        if spl <= 1:
            return 1, self.cfg.n_iters, 0
        n_full, rem = divmod(self.cfg.n_iters, spl)
        return spl, n_full, rem

    def train_doc_block(self, n_bucket_docs: int) -> int:
        """Fused-train doc block, clamped to the bucket (rounded to the
        sublane tile) so a small bucket doesn't pad to an empty block.
        Part of the SEMANTICS at spl>1 (the delayed-count partition)."""
        return min(self.cfg.train_doc_block, -(-n_bucket_docs // 8) * 8)

    def cache_key(self) -> tuple:
        """Everything a compiled program's identity depends on: the
        schedule's static shape signature (`types.bucket_signature`)
        plus `(cfg, backend)`.  Two plans with equal cache keys trace
        to identical programs — the serving layer's plan-cache key.
        NOTE the cache must hold DISTINCT jitted callables keyed on
        this (jit identity): a fresh `jax.jit(fn)` per request owns a
        fresh, empty trace cache and retraces every call no matter how
        the static args hash (serving/slda_service.py)."""
        return (bucket_signature(self.corpus), self.cfg, self.backend)

    def describe(self) -> dict:
        """The plan, human-readable — what launch/dryrun.py prints so a
        user can see WHY a route was picked before paying for a run."""
        bc, cfg = self.corpus, self.cfg
        spl, n_full, rem = self.sweep_schedule()
        slot = bc.padded_tokens()                  # per chain
        real = float(bc.real_tokens()) / (self.n_chains or 1)
        src_slots = bc.n_docs * bc.ctr_stride
        return {
            "backend": self.backend,
            "executor": self.executor,
            "chains": self.n_chains or 1,
            "docs_per_chain": bc.n_docs,
            "buckets": len(bc.buckets),
            "bucket_widths": list(bc.widths),
            "bucket_counts": list(bc.counts),
            "ctr_stride": bc.ctr_stride,
            "sweeps_per_launch": spl,
            "launches": n_full + (1 if rem else 0),
            "remainder_sweeps": rem,
            "count_refresh": "exact recount every EM boundary",
            "slot_tokens_per_sweep": int(slot),
            "real_tokens_per_sweep": int(real),
            "padded_slot_frac": round(1.0 - real / max(src_slots, 1), 4),
            "slot_vs_effective_tok_ratio": round(slot / max(real, 1.0), 3),
            "sampler_mode": cfg.sampler_mode,
            "sparse_topic_cap": min(cfg.sparse_topic_cap, cfg.n_topics),
        }

    # ---- the ONE chain-batched EM loop -----------------------------

    def init_states(self, keys_init):
        """Chain-batched init over the schedule: the SAME per-chain
        [D, ctr_stride] threefry draw as the padded path, carved along
        each chain's schedule.  Returns (state, z_fill): state.z is a
        tuple of per-bucket [M, D_b, N_b] assignments, state.ndt is
        [M, D, T] in ORIGINAL order, z_fill keeps the init values of
        the all-padding slots beyond each bucket's width."""
        bc, cfg = self.corpus, self.cfg
        d_m, S = bc.perm.shape[-1], bc.ctr_stride
        z_fill = jax.vmap(lambda k: jax.random.randint(
            k, (d_m, S), 0, cfg.n_topics, jnp.int32))(keys_init)
        z_b = tuple(bc.split_padded(z_fill))
        counts = lambda b, zb: jax.vmap(
            lambda t, m_, zz: counts_from_assignments(
                t, m_, zz, cfg.n_topics, cfg.vocab_size))(b.tokens,
                                                          b.mask, zb)
        pieces, ntw = [], 0.0
        for b, zb in zip(bc.buckets, z_b):
            nd, nw, _ = counts(b, zb)
            pieces.append(nd)
            ntw = ntw + nw           # ±1 integer adds — exact in any order
        eta = jnp.full((keys_init.shape[0], cfg.n_topics), cfg.mu,
                       jnp.float32)
        state = GibbsState(z=z_b, ndt=bc.merge_docs(pieces), ntw=ntw,
                           nt=jnp.sum(ntw, axis=-1), eta=eta)
        return state, z_fill

    def _refresh_and_solve(self, z_new_b, ndt):
        """THE EM boundary (the one copy): exact global count refresh —
        one recount of ntw from the new assignments, nt = ntw.sum(-1),
        ndt as the sweep left it (exact) — then the per-chain η ridge
        solve on ORIGINAL-order rows."""
        bc, cfg = self.corpus, self.cfg
        scatter = jax.vmap(lambda nw, t, m_, zz: nw.at[zz, t].add(m_))
        with jax.named_scope("count_refresh"), jax.named_scope("rebuild"):
            # one chain-batched scatter per bucket into one table:
            # integer adds, exact in any order
            ntw = jnp.zeros((bc.n_chains, cfg.n_topics, cfg.vocab_size),
                            jnp.float32)
            for b, zb in zip(bc.buckets, z_new_b):
                ntw = scatter(ntw, b.tokens, b.mask, zb)
            nt = jnp.sum(ntw, axis=-1)
        with jax.named_scope("eta_solve"):
            lengths = jnp.maximum(bc.lengths(), 1.0)
            eta = jax.vmap(lambda nd, l, yy: solve_eta(nd / l[:, None], yy,
                                                       self.cfg))(
                ndt, lengths, bc.y)
        return GibbsState(z=tuple(z_new_b), ndt=ndt, ntw=ntw, nt=nt,
                          eta=eta)

    def _inv_len_b(self):
        """Per-bucket 1/len rows — schedule-invariant; hoisted by
        train_em so the scan closes over it as a constant instead of
        re-deriving it every EM step."""
        bc = self.corpus
        return bc.split_docs(1.0 / jnp.maximum(bc.lengths(), 1.0))

    def _seed_sweep(self, state, ks, inv_len_b):
        """One seed-semantics sweep (spl=1): per-sweep threefry uniforms
        drawn at the padded [M, D, ctr_stride] shape (the bit-identity
        contract) and sliced along the schedule; one chain_axis sweep op
        per bucket."""
        from repro.kernels import ops   # local import (DESIGN.md §1)
        bc, cfg = self.corpus, self.cfg
        d_m, S = bc.perm.shape[-1], bc.ctr_stride
        uniforms = jax.vmap(lambda k: jax.random.uniform(k, (d_m, S)))(ks)
        u_b = bc.split_padded(uniforms)
        ndt_b = bc.split_docs(state.ndt)
        z_new_b, pieces = [], []
        for b, ub, zb, ndb, ilb in zip(bc.buckets, u_b, state.z, ndt_b,
                                       inv_len_b):
            z2, nd2 = ops.slda_gibbs_sweep(
                b.tokens, b.mask, ub, zb, ndb, b.y, ilb, state.ntw,
                state.nt, state.eta, alpha=cfg.alpha, beta=cfg.beta,
                rho=cfg.rho, supervised=True, use_pallas=self.use_pallas,
                chain_axis=True, sampler_mode=cfg.sampler_mode,
                sparse_topic_cap=cfg.sparse_topic_cap)
            z_new_b.append(z2)
            pieces.append(nd2)
        return z_new_b, bc.merge_docs(pieces)

    def _blocks_launch(self, state, ks, n_sweeps, inv_len_b):
        """One fused multi-sweep launch per bucket (chain grids intact,
        PRNG counter stride pinned to the source max_len).  Returns the
        launch's (z_new_b, ndt) for the EM boundary."""
        from repro.kernels import ops   # local import (DESIGN.md §1)
        bc, cfg = self.corpus, self.cfg
        d_m, S = bc.perm.shape[-1], bc.ctr_stride
        seeds = jax.vmap(lambda k: jax.random.randint(
            k, (d_m,), 0, jnp.iinfo(jnp.int32).max, jnp.int32))(ks)
        seeds_b = bc.split_docs(seeds)
        ndt_b = bc.split_docs(state.ndt)
        z_new_b, pieces = [], []
        for b, zb, ndb, sb, ilb in zip(bc.buckets, state.z, ndt_b,
                                       seeds_b, inv_len_b):
            z2, nd2 = ops.slda_train_sweeps(
                b.tokens, b.mask, zb, ndb, b.y, ilb, state.ntw, state.nt,
                state.eta, sb, alpha=cfg.alpha, beta=cfg.beta,
                rho=cfg.rho, n_sweeps=n_sweeps, supervised=True,
                doc_block=self.train_doc_block(b.tokens.shape[1]),
                use_pallas=self.use_pallas,
                product_form=cfg.product_form_sweeps, chain_axis=True,
                ctr_stride=S, sampler_mode=cfg.sampler_mode,
                sparse_topic_cap=cfg.sparse_topic_cap)
            z_new_b.append(z2)
            pieces.append(nd2)
        return z_new_b, bc.merge_docs(pieces)

    def _stair_staging(self):
        """Schedule-invariant staging of the stair trainer — the folded
        token/mask segments, per-row chain ids, folded y and 1/len —
        computed ONCE per trace (train_em hoists it so the launch scan
        closes over it as constants instead of re-folding the corpus
        every EM launch, which is what the pre-plan code did too)."""
        bc, cfg = self.corpus, self.cfg
        M, W = bc.n_chains, cfg.vocab_size
        d_m = bc.perm.shape[-1]
        (fold, unfold, sort, unsort, seg_r0, seg_n0,
         off) = _stair_layout(bc, M, W)
        return dict(
            fold=fold, unfold=unfold, sort=sort, unsort=unsort,
            seg_r0=seg_r0, seg_n0=seg_n0,
            tok_segs=[fold(s + off[:, None, None]) for s in
                      _stair_segments(bc, [b.tokens for b in bc.buckets])],
            mask_segs=[fold(s) for s in
                       _stair_segments(bc, [b.mask for b in bc.buckets])],
            chain_of_row=jnp.tile(jnp.arange(M, dtype=jnp.int32), d_m),
            y_f=fold(jnp.concatenate([b.y for b in bc.buckets], axis=1)),
            il_f=fold(jnp.concatenate(
                [1.0 / jnp.maximum(b.mask.sum(-1), 1.0)
                 for b in bc.buckets], axis=1)),
        )

    def _stair_launch(self, state, ks, n_sweeps, staging):
        """One STAIRCASE fused launch runs all in-launch sweeps for ALL
        chains (jnp route, multi-bucket): chains folded doc-major around
        a stacked [M·W, T] table, bucket widths walked as token-range
        segments over the live doc suffix — per-sweep step count stays
        N_max while slots collapse to the staircase.  The in-launch
        delayed-count partition is the WHOLE corpus (doc_block→D limit
        of the fused family).  Returns the launch's (z_new_b, ndt) for
        the EM boundary."""
        from repro.kernels.slda_train import slda_train_stair_jnp
        bc, cfg = self.corpus, self.cfg
        M = bc.n_chains
        d_m, S = bc.perm.shape[-1], bc.ctr_stride
        T, W = cfg.n_topics, cfg.vocab_size
        st = staging
        fold, unfold = st["fold"], st["unfold"]
        sort, unsort = st["sort"], st["unsort"]

        seeds = jax.vmap(lambda k: jax.random.randint(
            k, (d_m,), 0, jnp.iinfo(jnp.int32).max, jnp.int32))(ks)
        z_segs = [fold(s) for s in _stair_segments(bc, state.z)]
        z_segs_f, ndt_f = slda_train_stair_jnp(
            st["tok_segs"], st["mask_segs"], z_segs, st["seg_r0"],
            st["seg_n0"], fold(sort(seeds)), fold(sort(state.ndt)),
            st["y_f"], st["il_f"],
            jnp.swapaxes(state.ntw, 1, 2).reshape(M * W, T), state.nt,
            state.eta, st["chain_of_row"], alpha=cfg.alpha, beta=cfg.beta,
            rho=cfg.rho, vocab_size=W, ctr_stride=S, supervised=True,
            n_sweeps=n_sweeps, product_form=cfg.product_form_sweeps,
            sampler_mode=cfg.sampler_mode,
            sparse_topic_cap=cfg.sparse_topic_cap)
        z_new_b = _unstair_segments(bc, [unfold(z) for z in z_segs_f])
        return z_new_b, unsort(unfold(ndt_f))

    def n_boundaries(self) -> int:
        """EM boundaries this plan executes (count refresh + η solve
        points): one per sweep at spl=1, one per launch at spl>1 —
        the granularity at which an `em_hook` observes the state."""
        _, n_full, rem = self.sweep_schedule()
        return n_full + (1 if rem else 0)

    def train_em(self, k_sweeps, state0, *, em_hook=None, status0=None,
                 it_offset=0):
        """The stochastic-EM loop — the one copy.  spl=1 runs the seed
        path (threefry uniforms, η solve every sweep); spl>1 runs the
        fused-launch schedule through the plan's executor, with a
        remainder launch keeping total sweeps == cfg.n_iters exactly.

        `em_hook(state, it, status) -> (state, status)`, when given, is
        called at EVERY EM boundary *inside* the scan — the supervisor
        layer's attachment point (DESIGN.md §Fault-model): fault
        injection mutates the state, health probes fold per-chain flags
        into `status` (initialised from `status0`), all with zero extra
        host syncs; the accumulated status surfaces only in the return
        value `(state, status)`.  `it` is the EM-boundary index (sweep
        index at spl=1, launch index at spl>1) plus `it_offset`, so a
        supervisor running the loop round-by-round sees the single-run
        boundary indices.  With `em_hook=None` the loop is byte-for-byte
        the pre-hook program and returns `state` alone."""
        spl, n_full, rem = self.sweep_schedule()
        if spl == 1:
            inv_len_b = self._inv_len_b()   # hoisted: scan constant

            def em_step(carry, inp):
                state, status = carry
                ks, it = inp
                with jax.named_scope("gibbs_sweep"):
                    z_new_b, ndt = self._seed_sweep(state, ks, inv_len_b)
                state = self._refresh_and_solve(z_new_b, ndt)
                if em_hook is not None:
                    state, status = em_hook(state, it, status)
                return (state, status), None

            keys = jnp.moveaxis(jax.vmap(lambda k: jax.random.split(
                k, n_full))(k_sweeps), 0, 1)
            (state, status), _ = jax.lax.scan(
                em_step, (state0, status0),
                (keys, jnp.arange(n_full) + it_offset))
            return state if em_hook is None else (state, status)

        # schedule-invariant staging is hoisted HERE, once per trace —
        # the launch closures see it as scan constants
        if self.executor == "stair":
            sweeps = functools.partial(self._stair_launch,
                                       staging=self._stair_staging())
        else:
            sweeps = functools.partial(self._blocks_launch,
                                       inv_len_b=self._inv_len_b())

        def launch(state, ks, n_sweeps):
            with jax.named_scope("gibbs_sweep"):
                z_new_b, ndt = sweeps(state, ks, n_sweeps)
            return self._refresh_and_solve(z_new_b, ndt)
        keys = jnp.moveaxis(jax.vmap(lambda k: jax.random.split(
            k, n_full + (1 if rem else 0)))(k_sweeps), 0, 1)

        def launch_step(carry, inp):
            state, status = carry
            state = launch(state, inp[0], spl)
            if em_hook is not None:
                state, status = em_hook(state, inp[1], status)
            return (state, status), None

        state, status = state0, status0
        if n_full:
            (state, status), _ = jax.lax.scan(
                launch_step, (state, status),
                (keys[:n_full], jnp.arange(n_full) + it_offset))
        if rem:
            it = jnp.asarray(n_full) + it_offset
            state = launch(state, keys[-1], rem)
            if em_hook is not None:
                state, status = em_hook(state, it, status)
        return state if em_hook is None else (state, status)

    def _export(self, state) -> SLDAModel:
        """Per-chain (φ̂, η̂, train MSE/acc) — what crosses the chain
        boundary; ORIGINAL-order rows so reductions match the padded
        operand order."""
        from .gibbs import phi_hat   # lazy: gibbs lazily imports plan
        bc, cfg = self.corpus, self.cfg
        lengths = jnp.maximum(bc.lengths(), 1.0)
        zb = state.ndt / lengths[..., None]
        yhat = jax.vmap(lambda z, e: z @ e)(zb, state.eta)
        y = bc.y
        mse = jax.vmap(lambda yh, yy: jnp.mean((yh - yy) ** 2))(yhat, y)
        acc = jax.vmap(lambda yh, yy: jnp.mean(
            ((yh > 0.5) == (yy > 0.5)).astype(jnp.float32)))(yhat, y)
        phi = jax.vmap(lambda s: phi_hat(s, cfg))(state)
        return SLDAModel(phi=phi, eta=state.eta, train_mse=mse,
                         train_acc=acc)

    def train(self, keys):
        """Full chain-batched training from explicit per-chain keys [M]
        (the entry the multi-device runner uses with fold_in-derived
        keys).  Returns (GibbsState, SLDAModel), each with leading chain
        dim; state.z is merged back to padded [M, D, ctr_stride] in
        ORIGINAL order against the init draw."""
        assert self.n_chains is not None, \
            "train wants a chain-sharded schedule (use chained=True)"
        ks = jax.vmap(jax.random.split)(keys)           # [M, 2, key]
        state0, z_fill = self.init_states(ks[:, 0])
        state = self.train_em(ks[:, 1], state0)
        models = self._export(state)
        state = GibbsState(z=self.corpus.merge_padded(state.z, z_fill),
                           ndt=state.ndt, ntw=state.ntw, nt=state.nt,
                           eta=state.eta)
        return state, models

    # ---- prediction ------------------------------------------------

    def _predict_blocks(self, phi, z0, seeds):
        """Per-bucket chain-batched fused prediction launches over a
        SHARED corpus, counter stride pinned (the pallas route, and the
        degenerate 1-bucket jnp plan == the padded twins)."""
        from repro.kernels import ops   # local import (DESIGN.md §1)
        bc, cfg = self.corpus, self.cfg
        S = bc.ctr_stride
        z0_b = bc.split_padded(z0, d_axis=1)
        seeds_b = bc.split_docs(seeds, d_axis=1)
        avgs = []
        for b, z0b, sb in zip(bc.buckets, z0_b, seeds_b):
            d_idx = jnp.arange(b.tokens.shape[0])[:, None]
            ndt0 = jax.vmap(
                lambda z: jnp.zeros((b.tokens.shape[0], cfg.n_topics),
                                    jnp.float32)
                .at[d_idx, z].add(b.mask))(z0b)
            avg, _ = ops.slda_predict_sweeps(
                b.tokens, b.mask, z0b, ndt0, phi, sb,
                alpha=cfg.alpha, n_burnin=cfg.n_pred_burnin,
                n_samples=cfg.n_pred_samples,
                doc_block=cfg.pred_doc_block,
                use_pallas=self.use_pallas, chain_axis=True, ctr_stride=S,
                sampler_mode=cfg.sampler_mode,
                sparse_topic_cap=cfg.sparse_topic_cap)
            avgs.append(avg)
        return bc.merge_docs(avgs, d_axis=1)         # [M, D, T] original

    def _predict_stair(self, phi, z0, seeds):
        """The STAIRCASE prediction executor (jnp route, multi-bucket):
        chains folded DOC-MAJOR (row r = d·M + c) around one stacked
        [M·W, T] table so doc suffixes stay row suffixes; bucket widths
        walked as token-range segments inside each sweep — sequential
        step count stays N_max while executed slots collapse to the
        staircase."""
        from repro.kernels.slda_predict import slda_predict_stair_jnp
        bc, cfg = self.corpus, self.cfg
        M, T, W = phi.shape
        D, S = bc.n_docs, bc.ctr_stride
        phi_t = jnp.swapaxes(phi, -1, -2).reshape(M * W, T)
        # shared fold/offset math with the stair trainer (_stair_layout);
        # token/mask segments differ only in that the corpus here is
        # SHARED across chains (broadcast instead of per-chain fold)
        fold, _, sort, _, seg_r0, seg_n0, off = _stair_layout(bc, M, W)
        seeds_f = fold(sort(seeds))
        z0_b = bc.split_padded(z0, d_axis=1)         # [M, Db, Nb] sorted
        ndt0_f = fold(jnp.concatenate(
            [jax.vmap(lambda z: jnp.zeros((b.tokens.shape[0], T),
                                          jnp.float32)
                      .at[jnp.arange(b.tokens.shape[0])[:, None], z]
                      .add(b.mask))(zb)
             for b, zb in zip(bc.buckets, z0_b)], axis=1))

        seg_tok = [(tk[:, None, :] + off[None, :, None])
                   .reshape(tk.shape[0] * M, tk.shape[1])
                   for tk in _stair_segments(bc, [b.tokens
                                                  for b in bc.buckets])]
        seg_mask = [jnp.broadcast_to(mk[:, None, :], mk.shape[:1] + (M,)
                                     + mk.shape[1:])
                    .reshape(-1, mk.shape[1])
                    for mk in _stair_segments(bc, [b.mask
                                                   for b in bc.buckets])]
        seg_z0 = [jnp.swapaxes(zk, 0, 1).reshape(-1, zk.shape[-1])
                  for zk in _stair_segments(bc, z0_b)]

        avg_f = slda_predict_stair_jnp(
            seg_tok, seg_mask, seg_z0, seg_r0, seg_n0, seeds_f, ndt0_f,
            phi_t, alpha=cfg.alpha, n_burnin=cfg.n_pred_burnin,
            n_samples=cfg.n_pred_samples, ctr_stride=S,
            sampler_mode=cfg.sampler_mode,
            sparse_topic_cap=cfg.sparse_topic_cap)
        avg_sorted = jnp.swapaxes(avg_f.reshape(D, M, T), 0, 1)
        return _take_docs(avg_sorted, bc.inv_perm, 1)   # [M, D, T] orig

    def predict_zbar(self, keys, models: SLDAModel):
        """Per-chain posterior-mean topic mixtures z̄ [M, D, T]
        (ORIGINAL doc order) for every document of the plan's (SHARED)
        corpus, from explicit per-chain keys [M] — the serving entry:
        a prediction service caches z̄ per document and re-derives
        ŷ = z̄ᵀη̂ under whatever alive mask is CURRENT, so a mid-stream
        drop/revive stays exact for cached results too
        (serving/slda_service.py)."""
        bc, cfg = self.corpus, self.cfg
        assert bc.n_chains is None, \
            "predict wants a shared (flat) corpus schedule"
        D, S = bc.n_docs, bc.ctr_stride
        ks = jax.vmap(jax.random.split)(keys)           # [M, 2, key]
        z0 = jax.vmap(lambda k: jax.random.randint(
            k, (D, S), 0, cfg.n_topics, jnp.int32))(ks[:, 0])
        seeds = jax.vmap(lambda k: jax.random.randint(
            k, (D,), 0, jnp.iinfo(jnp.int32).max, jnp.int32))(ks[:, 1])
        run = (self._predict_stair if self.executor == "stair"
               else self._predict_blocks)
        with jax.named_scope("predict_sweeps"):
            ndt_avg = run(models.phi, z0, seeds)        # [M, D, T] orig
        lengths = jnp.maximum(bc.lengths(), 1.0)
        return jax.vmap(lambda nd: nd / lengths[:, None])(ndt_avg)

    def predict(self, keys, models: SLDAModel):
        """Every chain predicts every document of the plan's (SHARED)
        corpus → ŷ [M, D], from explicit per-chain keys [M].  Same key
        tree as the deleted per-path implementations, so every cell is
        bit-identical to the path it replaced."""
        zb = self.predict_zbar(keys, models)
        return jax.vmap(lambda z, e: z @ e)(zb, models.eta)   # Eq. (5)


jax.tree_util.register_pytree_node(
    ExecutionPlan,
    lambda p: ((p.corpus,), (p.cfg, p.backend)),
    lambda aux, ch: ExecutionPlan(corpus=ch[0], cfg=aux[0], backend=aux[1]),
)
