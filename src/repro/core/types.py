"""Core datatypes for sLDA and its embarrassingly parallel runner.

Everything is a registered pytree so it can flow through jit / vmap /
shard_map without ceremony.  Counts are kept in float32: they are small
integers in practice and float math keeps the samplers branch-free.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Array = Any


def _pytree(cls):
    """Register a dataclass as a pytree (all fields are children)."""
    fields = [f.name for f in dataclasses.fields(cls)]
    jax.tree_util.register_pytree_with_keys(
        cls,
        lambda obj: (
            [(jax.tree_util.GetAttrKey(n), getattr(obj, n)) for n in fields],
            None,
        ),
        lambda _, children: cls(*children),
    )
    return cls


@dataclasses.dataclass(frozen=True)
class SLDAConfig:
    """Hyperparameters of supervised LDA (McAuliffe & Blei 2008 notation)."""

    n_topics: int = 32
    vocab_size: int = 1024
    alpha: float = 0.1       # Dir prior on doc-topic θ_d
    beta: float = 0.01       # Dir prior on topic-word φ_t
    rho: float = 0.5         # response noise  y_d ~ N(ηᵀ z̄_d, ρ)
    mu: float = 0.0          # prior mean of η_t
    sigma: float = 10.0      # prior variance of η_t
    label_type: str = "continuous"   # "continuous" | "binary"
    n_iters: int = 60        # stochastic-EM iterations (Gibbs sweep + η solve)
    n_pred_burnin: int = 15  # test-time Gibbs burn-in sweeps
    n_pred_samples: int = 10 # test-time sweeps averaged for z̄
    use_pallas: bool = False # route sweeps through the slda TPU kernels
    pred_doc_block: int = 8  # doc block of the fused prediction kernel
    count_rebuild_every: int = 16  # exact ntw/nt rebuild cadence of the
                             # delta refresh (`apply_count_deltas`) in the
                             # CPU benchmarks' loops: iterations in between
                             # apply exact (z_old, z_new) delta updates
                             # instead of the full scatter.  0 = never
                             # rebuild, 1 = rebuild every sweep.  The plan
                             # (train_em) does not read it: it recounts
                             # ntw at every EM boundary.  Either refresh
                             # form is exact, so this knob is perf-only.
    sweeps_per_launch: int = 1  # training Gibbs sweeps fused into one
                             # kernel launch / scan body.  1 = seed
                             # semantics (threefry uniforms, η solve every
                             # sweep, globally sweep-frozen counts).  >1
                             # routes train_chain through the fused
                             # kernels/slda_train.py path: counter-hash
                             # PRNG, η solve between launches, and the
                             # AD-LDA block-local delayed-count refresh
                             # between in-launch sweeps (DESIGN.md
                             # §Train-kernel; tuned value in
                             # BENCH_slda_train.json).
    train_doc_block: int = 128  # doc block of the fused train kernel —
                             # also the delayed-count granularity
                             # (semantics, not just tiling, when
                             # sweeps_per_launch>1).  Bigger blocks are
                             # faster on CPU (fewer vmap lanes) AND less
                             # delayed (fewer blocks to defer across);
                             # train_chain clamps it to the corpus size.
    product_form_sweeps: bool = True  # fused multi-sweep launches
                             # (sweeps_per_launch > 1) sample the
                             # categorical from the plain product of
                             # positives times ONE Gaussian exp instead
                             # of three logs — same distribution, ~3x
                             # fewer transcendentals per token (the way
                             # the predict kernel already samples).
                             # Never applies at sweeps_per_launch=1,
                             # which keeps the seed log-form bits
                             # (DESIGN.md §Chain-batched).
    fuse_weighted_predict: bool = True  # Weighted Average predicts the
                             # test set and the full training set in ONE
                             # chain-batched fused pass over the
                             # concatenated corpus instead of two
                             # launches — same sweeps per document,
                             # half the sequential token-loop steps
                             # (the M x prediction pass is the paper's
                             # stated dominant cost).
    length_buckets: int = 0  # ragged-corpus execution (DESIGN.md
                             # §Ragged-execution): number of length
                             # buckets the bucketed entry points
                             # (`bucket_corpus`, the *_bucketed runners,
                             # launch/slda_parallel) split a corpus
                             # into, each padded to its own token-block-
                             # rounded max instead of the global max, so
                             # sweep compute scales with Σ true tokens.
                             # 0 keeps the padded path.  Schedules are
                             # built from concrete lengths (outside
                             # jit); the padded core paths ignore this
                             # knob.  Bit-identical per document to the
                             # padded path at sweeps_per_launch=1.
    bucket_token_block: int = 8  # bucket widths round up to this many
                             # tokens (sublane-friendly; smaller = less
                             # intra-bucket padding, more distinct
                             # widths to compile)
    bucket_overhead_docs: float = 0.0  # per-bucket fixed cost, in
                             # document rows, fed to the schedule DP
                             # (`bucket_corpus`).  The jnp-route STAIR
                             # executors walk the bucket widths as
                             # token-range segments inside each sweep
                             # (step count stays N_max), so extra
                             # buckets are nearly free there — measured
                             # best at 0 (BENCH_slda_ragged.json;
                             # `length_buckets` still caps the count).
                             # The per-bucket launch route (pallas)
                             # re-runs its token loop per bucket, where
                             # a step costs ~a hundred folded doc rows
                             # on CPU — raise this knob if that route
                             # is the hot one.  0 minimizes padded
                             # slots alone.
    chains_per_device: int = 1  # launch-level knob: the shard_map
                             # runner trains chains_per_device chains
                             # per mesh slice through the chain-batched
                             # ops, so M = mesh axis x chains_per_device
                             # decouples the paper's M from the device
                             # count (still zero collectives until the
                             # final prediction gather).
    sampler_mode: str = "dense"  # per-token categorical draw strategy
                             # (DESIGN.md §Sparse-sampler): "dense" —
                             # the seed draw, O(T²) matmul prefix sum
                             # per token, bit-identical to every prior
                             # PR; "sparse" — the two-stage draw: a
                             # sparse bucket over the word's occupied
                             # topics (per-word index built at launch /
                             # refresh boundaries, `sparse_topic_cap`
                             # wide) plus a blocked hierarchical draw
                             # over the residual mass, distributionally
                             # exact for ANY index content and
                             # bitwise-reproducible within the mode
                             # (kernel ≡ twin ≡ oracle).  One uniform
                             # per token either way, so `ctr_stride`
                             # accounting and bucketed/padded parity
                             # carry over unchanged.
    sparse_topic_cap: int = 32  # width of the per-word topic index the
                             # sparse sampler gathers through (top-cap
                             # occupied topics per word).  Exactness
                             # never depends on it — overflow mass is
                             # simply drawn through the residual stage —
                             # so it is perf-only; clamped to n_topics.

    def resolve_backend(self, devices=None) -> str:
        """The ONE backend-routing decision (DESIGN.md §Execution-plan).

        Returns "jnp" (the batched-jnp twins — the CPU fast path),
        "pallas" (compiled kernels — every device is a TPU), or
        "pallas-interpret" (use_pallas forced on a non-TPU backend —
        correct but slow; what the kernel-parity tests exercise).
        `devices=None` asks the default backend; the multi-device
        runner passes its mesh's devices.
        """
        if not self.use_pallas:
            return "jnp"
        return ("pallas" if devices_support_pallas(devices)
                else "pallas-interpret")


def devices_support_pallas(devices=None) -> bool:
    """True when every target device compiles the sLDA Pallas kernels
    natively (TPU).  Shared predicate behind `SLDAConfig.resolve_backend`,
    `kernels.ops`' interpret-mode switch, and the launch runner's
    auto_pallas flip — the one copy of the platform check."""
    if devices is None:
        return jax.default_backend() == "tpu"
    return all(d.platform == "tpu" for d in devices)


@_pytree
@dataclasses.dataclass
class Corpus:
    """A padded bag of documents.

    tokens  : int32[D, N]  word ids, padding value arbitrary where mask==0
    mask    : float32[D, N] 1.0 on real tokens
    y       : float32[D]   document labels (binary labels stored as 0/1)
    """

    tokens: Array
    mask: Array
    y: Array

    @property
    def n_docs(self) -> int:
        return self.tokens.shape[0]

    @property
    def max_len(self) -> int:
        return self.tokens.shape[1]

    def lengths(self) -> Array:
        return jnp.sum(self.mask, axis=-1)


@_pytree
@dataclasses.dataclass
class GibbsState:
    """Mutable state of one collapsed-Gibbs sLDA chain."""

    z: Array       # int32[D, N]   token-topic assignments
    ndt: Array     # float32[D, T] doc-topic counts
    ntw: Array     # float32[T, W] topic-word counts
    nt: Array      # float32[T]    topic totals
    eta: Array     # float32[T]    regression weights


@_pytree
@dataclasses.dataclass
class SLDAModel:
    """What a trained chain exports: enough to predict, nothing more.

    This is the only thing that ever crosses a chain boundary — it is what
    makes the parallel algorithm communication-free during training.
    """

    phi: Array     # float32[T, W] topic-word distributions  φ̂
    eta: Array     # float32[T]    regression weights        η̂
    train_mse: Array   # float32[] training-set MSE (Weighted Average weight)
    train_acc: Array   # float32[] training-set accuracy (binary labels)


# ------------------------------------------------- ragged execution layer

def _take_docs(arr, idx, d_axis):
    """Gather document rows: idx [D'] (any d_axis) or [M, D'] (then the
    doc axis is 1 and arr carries the matching leading chain dim)."""
    if idx.ndim == 1:
        return jnp.take(arr, idx, axis=d_axis)
    assert d_axis == 1, d_axis
    return jax.vmap(lambda a, i: jnp.take(a, i, axis=0))(arr, idx)


@dataclasses.dataclass
class BucketedCorpus:
    """A corpus reorganized for length-bucketed (ragged) execution.

    Documents are sorted by true length and grouped into buckets; bucket
    `b` holds a contiguous run of the sorted order, padded to its OWN
    token width `widths[b]` (a token_block multiple of the longest doc in
    the bucket) instead of the global max.  The fused train/predict
    launches then run once per bucket, so sweep compute and padded
    memory scale with Σ_b D_b·N_b ≈ Σ true tokens rather than D·N_max
    (DESIGN.md §Ragged-execution).

    buckets   : per-bucket `Corpus` (tokens [.., D_b, N_b]), rows in
                sorted order; a leading chain dim M rides along when the
                source was a chain-sharded corpus [M, D, N].
    perm      : int32 [D] (or [M, D]) — sorted position i holds original
                document perm[i].
    inv_perm  : int32 [D] (or [M, D]) — original document d sits at
                sorted position inv_perm[d].
    ctr_stride: static int — the SOURCE corpus max_len.  Pinned as the
                PRNG counter stride of every bucketed launch so each
                (doc, sweep, token) triple draws the uniform it would in
                the unbucketed launch; with per-document hash seeds this
                is what makes bucketed execution bit-identical per
                document (the inverse-permutation contract: outputs are
                restored to original order via `merge_docs`).

    Registered as a pytree whose static aux is `ctr_stride` plus the
    bucket structure, so it can be passed through jit/shard_map; the
    schedule itself must be BUILT from concrete arrays (`bucket_corpus`).
    """

    buckets: tuple
    perm: Array
    inv_perm: Array
    ctr_stride: int
    identity: bool = False   # static: the DEGENERATE 1-bucket schedule
                             # with an identity permutation (the padded
                             # path as a plan cell — core.plan.as_bucketed).
                             # Row plumbing is a no-op then, so the
                             # degenerate plan compiles to exactly the
                             # padded program (same bits, zero gather
                             # overhead).

    @property
    def _trivial(self) -> bool:
        return self.identity and len(self.buckets) == 1

    # ---- static schedule facts (shapes only — safe under tracing)

    @property
    def widths(self) -> tuple:
        return tuple(b.tokens.shape[-1] for b in self.buckets)

    @property
    def counts(self) -> tuple:
        return tuple(b.tokens.shape[-2] for b in self.buckets)

    @property
    def n_docs(self) -> int:
        return sum(self.counts)

    @property
    def n_chains(self):
        """Leading chain dim of a chain-sharded schedule (None if flat)."""
        t = self.buckets[0].tokens
        return t.shape[0] if t.ndim == 3 else None

    @property
    def max_len(self) -> int:
        return self.ctr_stride

    def padded_tokens(self) -> int:
        """Token-loop slots the bucketed schedule executes (per chain)."""
        return sum(d * w for d, w in zip(self.counts, self.widths))

    def real_tokens(self) -> Array:
        return sum(b.mask.sum() for b in self.buckets)

    def lengths(self) -> Array:
        """True doc lengths in ORIGINAL order, [D] (or [M, D])."""
        d_axis = self.perm.ndim - 1
        return self.merge_docs([b.mask.sum(-1) for b in self.buckets],
                               d_axis=d_axis)

    @property
    def y(self) -> Array:
        """Labels in ORIGINAL order (buckets store them sorted)."""
        return self.merge_docs([b.y for b in self.buckets],
                               d_axis=self.perm.ndim - 1)

    # ---- row plumbing between original order and the bucketed layout

    def split_docs(self, arr, d_axis=None):
        """Original-order doc rows [.., D, ...] → per-bucket pieces."""
        if self._trivial:
            return [arr]
        if d_axis is None:
            d_axis = self.perm.ndim - 1
        srt = _take_docs(arr, self.perm, d_axis)
        out, o = [], 0
        for c in self.counts:
            sl = (slice(None),) * d_axis + (slice(o, o + c),)
            out.append(srt[sl])
            o += c
        return out

    def merge_docs(self, pieces, d_axis=None):
        """Per-bucket doc rows → one array in ORIGINAL order."""
        pieces = list(pieces)
        if self._trivial:
            return pieces[0]
        if d_axis is None:
            d_axis = self.perm.ndim - 1
        return _take_docs(jnp.concatenate(pieces, axis=d_axis),
                          self.inv_perm, d_axis)

    def split_padded(self, arr, d_axis=None):
        """[.., D, ctr_stride] original order → per-bucket [.., D_b, N_b]
        (rows gathered, token tail truncated to the bucket width)."""
        if self._trivial and self.widths[0] == self.ctr_stride:
            return [arr]
        if d_axis is None:
            d_axis = self.perm.ndim - 1
        return [p[..., :w] for p, w in zip(self.split_docs(arr, d_axis),
                                           self.widths)]

    def merge_padded(self, pieces, fill, d_axis=None):
        """Per-bucket [.., D_b, N_b] → [.., D, ctr_stride] original order;
        token columns beyond each bucket's width come from `fill`
        (original order) — they are all-padding slots, which the
        unbucketed launch leaves at their input values."""
        pieces = list(pieces)
        if self._trivial and pieces[0].shape[-1] == self.ctr_stride:
            return pieces[0]
        if d_axis is None:
            d_axis = self.perm.ndim - 1
        fills = self.split_docs(fill, d_axis)
        full = [jnp.concatenate([p, f[..., p.shape[-1]:]], axis=-1)
                for p, f in zip(pieces, fills)]
        return self.merge_docs(full, d_axis)


jax.tree_util.register_pytree_node(
    BucketedCorpus,
    lambda bc: ((bc.buckets, bc.perm, bc.inv_perm),
                (bc.ctr_stride, bc.identity)),
    lambda aux, ch: BucketedCorpus(buckets=tuple(ch[0]), perm=ch[1],
                                   inv_perm=ch[2], ctr_stride=aux[0],
                                   identity=aux[1]),
)


def bucket_signature(bc: BucketedCorpus) -> tuple:
    """The static shape signature of a bucketed schedule — everything
    the corpus contributes to a compiled program's identity: one
    (width, count) pair per bucket plus the PRNG counter stride, the
    chain layout, and the degenerate-identity flag.  Hashable; two
    schedules with equal signatures trace to identical programs, so a
    prediction program compiled for one micro-batch serves every later
    batch with the same signature (the serving plan-cache key —
    serving/slda_service.py)."""
    return (tuple(zip(bc.widths, bc.counts)), bc.ctr_stride,
            bc.n_chains, bc.identity)


def _dp_bucket_cuts(segs, max_buckets: int, overhead: float):
    """Optimal contiguous grouping of width segments into ≤ max_buckets
    buckets, minimizing the modeled sweep cost Σ_b (D_b + overhead)·N_b.

    segs: [(count, width), ...] with strictly increasing widths (docs
    sorted by length, compressed to runs of equal rounded width — a cut
    inside a run can never pay, so these are the only candidate cuts).
    `overhead` is the per-bucket fixed cost in document-row units: each
    extra bucket re-runs the sequential token loop for its width, and on
    CPU a scan step has a fixed cost worth ~a hundred folded doc rows
    (measured in BENCH_slda_ragged.json — equal-count quantile buckets
    lose exactly because they ignore this term).  overhead=0 minimizes
    padded slots alone (maximal fragmentation up to max_buckets).
    """
    S = len(segs)
    max_b = max(1, min(max_buckets, S))
    pref = [0]
    for c, _ in segs:
        pref.append(pref[-1] + c)
    INF = float("inf")
    # dp[b][j]: best cost of covering the first j segments with b buckets
    dp = [[INF] * (S + 1) for _ in range(max_b + 1)]
    cut = [[0] * (S + 1) for _ in range(max_b + 1)]
    dp[0][0] = 0.0
    for b in range(1, max_b + 1):
        for j in range(1, S + 1):
            w = segs[j - 1][1]
            for i in range(j):
                if dp[b - 1][i] == INF:
                    continue
                c = dp[b - 1][i] + (pref[j] - pref[i] + overhead) * w
                if c < dp[b][j]:
                    dp[b][j] = c
                    cut[b][j] = i
    b_best = min(range(1, max_b + 1), key=lambda b: dp[b][S])
    bounds, j = [], S
    for b in range(b_best, 0, -1):
        bounds.append(j)
        j = cut[b][j]
    return list(reversed(bounds))                   # segment end indices


def bucket_corpus(corpus: Corpus, n_buckets: int = 8, *,
                  token_block: int = 8,
                  overhead_docs: float = 96.0) -> BucketedCorpus:
    """Build the length-bucketed schedule for `corpus` (host-side).

    Documents are stably argsorted by true length (per chain for a
    chain-sharded [M, D, N] corpus — every chain shares the same bucket
    SIZES so the chain-batched grids stay rectangular, while each chain
    gets its own permutation) and partitioned into AT MOST `n_buckets`
    contiguous groups by a cost-model DP (`_dp_bucket_cuts`): each
    group is padded to its token_block-rounded max length (max across
    chains), and the partition minimizes Σ_b (D_b + overhead_docs)·N_b
    — padded slots plus the per-bucket token-loop overhead, so heavy
    tails get cut off into their own (small) wide bucket instead of
    fragmenting the bulk into equal-count quantiles.  The degenerate
    all-same-length corpus collapses to ONE bucket (the padded path
    plus a no-op permutation).

    Shapes are data-dependent, so this runs on CONCRETE arrays only —
    call it outside jit (the result is a pytree you can pass in).
    """
    try:
        mask = np.asarray(corpus.mask)
    except jax.errors.TracerArrayConversionError as e:  # pragma: no cover
        raise ValueError(
            "bucket_corpus needs concrete lengths — build the schedule "
            "outside jit and pass the BucketedCorpus in") from e
    lens = mask.sum(-1).astype(np.int64)             # [D] or [M, D]
    chain = lens.ndim == 2
    D = lens.shape[-1]
    src_n = corpus.tokens.shape[-1]
    nb = max(1, min(int(n_buckets), D))

    perm = np.argsort(lens, axis=-1, kind="stable").astype(np.int32)
    lens_sorted = np.take_along_axis(lens, perm, axis=-1)

    # per sorted position: the rounded width it needs (max across chains
    # — each chain's sorted lengths ascend, so the column max ascends)
    colmax = lens_sorted.max(axis=0) if chain else lens_sorted
    round_w = np.minimum(
        src_n, np.maximum(token_block,
                          -(-colmax // token_block) * token_block))
    # compress to runs of equal width — the only candidate cut points
    segs = []
    for w in round_w:
        if segs and segs[-1][1] == int(w):
            segs[-1][0] += 1
        else:
            segs.append([1, int(w)])
    segs = [(c, w) for c, w in segs]
    ends = _dp_bucket_cuts(segs, nb, float(overhead_docs))
    widths, counts, o = [], [], 0
    for e in ends:
        cnt = sum(c for c, _ in segs[o:e])
        widths.append(segs[e - 1][1])
        counts.append(cnt)
        o = e

    inv_perm = np.argsort(perm, axis=-1, kind="stable").astype(np.int32)
    perm_j = jnp.asarray(perm)
    d_axis = 1 if chain else 0
    srt = lambda x: _take_docs(x, perm_j, d_axis)
    tok_s, mask_s, y_s = srt(corpus.tokens), srt(corpus.mask), srt(corpus.y)
    buckets, o = [], 0
    for c, w in zip(counts, widths):
        sl = (slice(None),) * d_axis + (slice(o, o + c), slice(None, w))
        buckets.append(Corpus(tokens=tok_s[sl], mask=mask_s[sl],
                              y=y_s[sl[:-1]]))
        o += c
    return BucketedCorpus(buckets=tuple(buckets), perm=perm_j,
                          inv_perm=jnp.asarray(inv_perm),
                          ctr_stride=src_n)


def partition(corpus: Corpus, m: int) -> Corpus:
    """Split a corpus into M equal shards: [D, ...] → [M, D/M, ...].

    The paper partitions uniformly at random; callers should pre-shuffle.
    D must be divisible by M (pad the corpus if not).
    """
    if corpus.n_docs % m:
        raise ValueError(f"{corpus.n_docs} docs not divisible by {m} shards")
    reshape = lambda x: x.reshape((m, corpus.n_docs // m) + x.shape[1:])
    return Corpus(tokens=reshape(corpus.tokens), mask=reshape(corpus.mask),
                  y=reshape(corpus.y))


def _concat_corpora(a: Corpus, b: Corpus) -> Corpus:
    """Stack two corpora along the doc axis (padding to a common max_len)
    so one fused prediction pass covers both."""
    n = max(a.max_len, b.max_len)
    padn = lambda x, w: jnp.pad(x, ((0, 0), (0, w))) if w else x
    return Corpus(
        tokens=jnp.concatenate([padn(a.tokens, n - a.max_len),
                                padn(b.tokens, n - b.max_len)]),
        mask=jnp.concatenate([padn(a.mask, n - a.max_len),
                              padn(b.mask, n - b.max_len)]),
        y=jnp.concatenate([a.y, b.y]))


def _stair_segments(bc, pieces):
    """Per-bucket token-padded pieces [.., D_b, N_b] → stair segments:
    segment k holds token columns [w_{k-1}, w_k) of buckets k..K (the
    docs still alive there — a suffix of the sorted order)."""
    out, w_prev = [], 0
    for k, w in enumerate(bc.widths):
        out.append(jnp.concatenate([p[..., w_prev:w] for p in pieces[k:]],
                                   axis=-2))
        w_prev = w
    return out


def _unstair_segments(bc, segs):
    """Inverse of _stair_segments: stair segments [.., D_k, L_k] back to
    per-bucket token-padded pieces [.., D_b, N_b]."""
    starts = np.cumsum([0] + list(bc.counts))
    out = []
    for j, c in enumerate(bc.counts):
        cols = []
        for k in range(j + 1):
            a = int(starts[j] - starts[k])
            cols.append(segs[k][..., a:a + c, :])
        out.append(jnp.concatenate(cols, axis=-1))
    return out


def counts_from_assignments(tokens: Array, mask: Array, z: Array,
                            n_topics: int, vocab_size: int):
    """Exact (ndt, ntw, nt) from the current assignments. Used to refresh the
    delayed topic-word table between document-parallel sweeps."""
    d_idx = jnp.arange(tokens.shape[0])[:, None]
    ndt = jnp.zeros((tokens.shape[0], n_topics), jnp.float32)
    ndt = ndt.at[d_idx, z].add(mask)
    ntw = jnp.zeros((n_topics, vocab_size), jnp.float32)
    ntw = ntw.at[z, tokens].add(mask)
    return ndt, ntw, jnp.sum(ntw, axis=-1)


def apply_count_deltas(ntw: Array, nt: Array, tokens: Array, mask: Array,
                       z_old: Array, z_new: Array, cap: int | None = None):
    """Exact incremental (ntw, nt) refresh from one sweep's reassignments.

    The plan's EM boundary does not call this: it recounts ntw from the
    new assignments (`ExecutionPlan._refresh_and_solve`).  It serves
    `core.gibbs.sweep(exact_rebuild=False)` and the CPU benchmarks.

    Only tokens whose topic actually changed carry weight (typically few,
    late in sampling — Magnusson et al., sparse partially collapsed
    samplers), so the scatter is issued in **changed-token compaction**
    form: gather the positions where `z_old != z_new` into a static-width
    buffer of `cap` slots and scatter only those ±1 updates, instead of a
    dense [D·N]-index 2-scatter that is mostly zero-weight no-ops.  If a
    sweep reassigns more than `cap` tokens (early sweeps), a `lax.cond`
    falls back to the dense form — exactness never depends on the cap.
    Under `jax.vmap` that predicate is batched, so both forms run.

    cap=None picks the dense form on CPU, where the nonzero+gather
    overhead makes the compacted branch ~3× a dense scatter even at 5 %
    change (DESIGN.md §Train-kernel), and max(128, D·N/8) slots on other
    backends (never measured as a winner there).  Pass `cap=0` to force
    dense, or an explicit slot count to force compaction.  Counts stay
    exact either way: ±1.0 float32 updates are lossless below 2^24.
    """
    changed = mask * (z_new != z_old).astype(mask.dtype)
    flat = changed.ravel()
    total = flat.shape[0]
    if cap is None:
        cap = 0 if jax.default_backend() == "cpu" else max(128, total // 8)
    cap = int(min(cap, total))

    def dense(_):
        with jax.named_scope("dense"):
            ntw2 = (ntw.at[z_old, tokens].add(-changed)
                    .at[z_new, tokens].add(changed))
            nt2 = (nt + jnp.zeros_like(nt).at[z_new].add(changed)
                   - jnp.zeros_like(nt).at[z_old].add(changed))
            return ntw2, nt2

    if cap <= 0 or cap >= total:
        return dense(None)

    n_changed = jnp.sum(flat > 0)
    w_all, zo_all, zn_all = tokens.ravel(), z_old.ravel(), z_new.ravel()

    def compact(_):
        with jax.named_scope("compact"):
            idx = jnp.nonzero(flat > 0, size=cap, fill_value=0)[0]
            wt = (jnp.arange(cap) < n_changed).astype(ntw.dtype)
            w, zo, zn = w_all[idx], zo_all[idx], zn_all[idx]
            ntw2 = ntw.at[zo, w].add(-wt).at[zn, w].add(wt)
            nt2 = (nt + jnp.zeros_like(nt).at[zn].add(wt)
                   - jnp.zeros_like(nt).at[zo].add(wt))
            return ntw2, nt2

    return jax.lax.cond(n_changed <= cap, compact, dense, None)


def topic_occupancy_index(table_t: Array, cap: int):
    """Per-word top-`cap` occupied-topic index for the sparse sampler.

    `table_t` is any `[..., W, T]` word-major table — `ntw` transposed for
    training, `phi_t` (or a chain-stacked `[M·W, T]` stair table) for
    prediction.  Returns `(idx, vmask, occm)`:

      * ``idx``   int32 `[..., W, cap]` — the word's top-`cap` topics by
        mass (argsort keeps the entries DISTINCT, which is what makes the
        support split below an identity);
      * ``vmask`` f32 `[..., W, cap]` — 1 where the indexed entry carries
        positive mass, 0 for slots past the word's true occupancy;
      * ``occm``  f32 `[..., W, T]` — the dense 0/1 membership mask of the
        valid indexed topics.

    The sparse draw splits the exact dense weights p as
    ``sv = take_along(p, idx)·vmask`` (sparse bucket) and
    ``rv = p·(1−occm)`` (residual); scatter(sv)+rv == p holds exactly in
    float32 for ANY index content, so a stale index (built from the
    launch-frozen table while counts evolve in-launch) changes WHICH
    bucket serves a topic, never the distribution.  `cap` is perf-only
    and clamped to T.
    """
    *lead, w_dim, t_dim = table_t.shape
    cap = int(min(cap, t_dim))
    flat = table_t.reshape((-1, w_dim, t_dim))
    idx = jnp.argsort(-flat, axis=-1)[..., :cap].astype(jnp.int32)
    vals = jnp.take_along_axis(flat, idx, axis=-1)
    vmask = (vals > 0).astype(jnp.float32)
    b = jnp.arange(flat.shape[0])[:, None, None]
    w = jnp.arange(w_dim)[None, :, None]
    # idx entries are distinct per word, so add == set on the zero init
    occm = jnp.zeros(flat.shape, jnp.float32).at[b, w, idx].add(vmask)
    shape = tuple(lead) + (w_dim,)
    return (idx.reshape(shape + (cap,)), vmask.reshape(shape + (cap,)),
            occm.reshape(shape + (t_dim,)))


def topic_occupancy(table_t: Array) -> Array:
    """Number of positive-mass topics per word (`[..., W]`), for the
    bench occupancy column and the dry-run why-lines."""
    return jnp.sum((table_t > 0).astype(jnp.int32), axis=-1)
