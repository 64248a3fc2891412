"""Continuous-batching sLDA prediction service (ROADMAP item 1).

The paper's zero-communication chains make per-request fan-out to M
chains embarrassingly parallel; this module is the serving surface that
routes real request traffic through the PR 5 `ExecutionPlan` layer:

  * **micro-batcher** — incoming ragged documents accumulate into
    fixed-shape micro-batches.  Every batch has the SAME slot layout: a
    width *ladder* of bucket rungs (ascending token widths, the last
    rung always `max_doc_len`) with a fixed per-rung slot *quota*
    (`calibrate_slots` picks both from a sample of the traffic's length
    distribution via the same cost-model DP that `bucket_corpus` uses).
    A document occupies one slot of the smallest rung that fits it
    (escalating to a wider rung when its own is full); unused slots are
    masked-out dummies.  The payoff: every dispatch has ONE static
    bucket signature, so steady-state traffic never retraces.

  * **retrace-free plan cache** — compiled programs are cached as
    DISTINCT `jax.jit` callables in a dict keyed on
    `ExecutionPlan.cache_key()` (the bucket-width signature +
    (cfg, backend)).  This is jit *identity*, not static-arg hashing: a
    fresh `jax.jit(fn)` per request owns a fresh, empty trace cache and
    retraces every call no matter how the static args hash — the cache
    must hold the callables themselves.  A trace counter incremented
    from the traced function body (a Python side effect that fires once
    per trace, never per call) makes the no-retrace property observable
    and assertable (tests, BENCH_slda_serving.json).

  * **result cache** — per-document posterior-mean topic mixtures z̄
    (theta) and per-chain ŷ are cached by content hash; a repeat
    document is served without occupying a slot.  The cache stores
    PER-CHAIN values, never the combined scalar, so…

  * **mid-stream drop/revive is exact** — `chain_weights` rides as a
    jit ARGUMENT of every cached callable (dropping a chain cannot
    retrace), and combination happens under the weights current at
    serve time — for fresh batches inside the compiled dispatch, for
    cache hits on the host via the same `core.combine` rules.  Because
    chains share nothing, serving the surviving sub-ensemble is
    bit-identical to an ensemble that never contained the dead chain
    (DESIGN.md §Fault-model).

Numerical contract: a dispatch is exactly `plan.predict_zbar` over the
micro-batch corpus — the serving machinery (slot packing, caches,
combine plumbing) adds ZERO deviation versus calling the plan layer
directly, and the bucketed slot layout is bit-identical per document to
the padded (`bucketed=False`) layout by the `ctr_stride` pinning of
DESIGN.md §Ragged-execution (tests/test_slda_serving.py).

Robustness layer (DESIGN.md §Serving-robustness): the service survives
traffic and faults without ever giving up the contracts above —

  * **admission control + deadlines** — the pending queue is bounded
    (`max_pending`), a token bucket rate-limits intake
    (`rate_limit_per_s`/`rate_burst`), and every request may carry a
    deadline.  Over-limit requests are SHED with a typed `Result`
    status (never an opaque exception), `_pack` orders pending work
    earliest-deadline-first, and an expired request is shed BEFORE it
    can occupy a slot.  `drain(deadline_s=...)` bounds how long a
    shutdown/flush storm can run.
  * **serve-time health + degraded mode** — model tables are screened
    with `core.supervisor.model_status` at load and at every hot
    reload, and per-chain ŷ is screened at dispatch
    (`robust_checks`); an unhealthy chain is auto-quarantined through
    the same `chain_weights`-as-jit-argument path as a manual
    `drop_chain`, so degradation is EXACT (survivors bit-identical to
    a clean service) and retrace-free.  An all-dead ensemble falls
    back to the unmasked combine + RuntimeWarning (`core.combine`'s
    PR 6 semantics) instead of dividing by zero.
  * **hot checkpoint reload** — `reload_from_checkpoint` performs an
    epoch-versioned atomic model swap: validate the manifest, load,
    screen, THEN swap; a torn/`BadZipFile`/mislabelled/wrong-M
    checkpoint is rejected with the old epoch kept serving.  The
    result cache is keyed on (content hash, model epoch), so a swap
    can never serve stale predictions, and because models ride as jit
    ARGUMENTS a swap never retraces.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import (TORN_CHECKPOINT_ERRORS, latest_step,
                              restore_checkpoint)
from repro.core.combine import median, simple_average, weighted_average
from repro.core.plan import as_bucketed, build_plan
from repro.core.supervisor import (MODEL_FAULTS, F_NAN_YHAT,
                                   describe_status, model_status)
from repro.core.types import (BucketedCorpus, Corpus, SLDAConfig, SLDAModel,
                              _dp_bucket_cuts)

# ------------------------------------------------------- typed outcomes

#: `Result.status` values — every submitted request id resolves to ONE
#: of these (invalid documents are the exception: they raise
#: `InvalidDocument` and never get an id).
STATUS_OK = "ok"
STATUS_SHED_QUEUE = "shed_queue_full"    # bounded queue at capacity
STATUS_SHED_RATE = "shed_rate_limit"     # token bucket empty
STATUS_EXPIRED = "expired"               # deadline passed before dispatch
SHED_STATUSES = (STATUS_SHED_QUEUE, STATUS_SHED_RATE, STATUS_EXPIRED)


class InvalidDocument(ValueError):
    """Typed `submit()` rejection — the request can NEVER be served
    (malformed payload), as opposed to the shed statuses (well-formed
    but dropped by overload policy).  `reason` is one of "empty_doc",
    "doc_too_long", "bad_token_id"; catching plain ValueError keeps
    working."""

    def __init__(self, reason: str, detail: str):
        super().__init__(f"{reason}: {detail}")
        self.reason = reason


# ------------------------------------------------------------ calibration

def calibrate_slots(lengths, batch_docs: int, max_doc_len: int, *,
                    n_buckets: int = 4, token_block: int = 8,
                    overhead_docs: float = 0.0):
    """Pick the service's (width ladder, slot quota) from a sample of
    document lengths — the same cost-model DP as `bucket_corpus`
    (`_dp_bucket_cuts`: minimize Σ_b (D_b + overhead)·N_b over
    contiguous cuts of the sorted length profile), then scale the
    bucket document counts to `batch_docs` slots by largest remainder.
    The widest rung is forced to `max_doc_len` (and keeps ≥1 slot) so
    every admissible request fits some rung.  Returns
    (widths, quota) — equal-length tuples, sum(quota) == batch_docs."""
    lens = np.clip(np.asarray(lengths).ravel(), 1, max_doc_len)
    if batch_docs < 1:
        raise ValueError("batch_docs must be >= 1")
    lens_sorted = np.sort(lens)
    round_w = np.minimum(
        max_doc_len,
        np.maximum(token_block, -(-lens_sorted // token_block)
                   * token_block)).astype(int)
    segs = []
    for w in round_w:
        if segs and segs[-1][1] == int(w):
            segs[-1][0] += 1
        else:
            segs.append([1, int(w)])
    segs = [(c, w) for c, w in segs]
    ends = _dp_bucket_cuts(segs, max(1, min(n_buckets, batch_docs)),
                           float(overhead_docs))
    widths, counts, o = [], [], 0
    for e in ends:
        counts.append(sum(c for c, _ in segs[o:e]))
        widths.append(segs[e - 1][1])
        o = e
    widths[-1] = max_doc_len

    # largest-remainder scaling of counts → quota, each rung >= 1 slot
    total = float(sum(counts))
    raw = [batch_docs * c / total for c in counts]
    quota = [max(1, int(f)) for f in raw]
    while sum(quota) > batch_docs:        # too many rungs for the slots:
        widths.pop(0)                     # merge the narrowest rung up
        quota.pop(0)
        raw.pop(0)
    rema = sorted(range(len(quota)), key=lambda i: raw[i] - int(raw[i]),
                  reverse=True)
    i = 0
    while sum(quota) < batch_docs:
        quota[rema[i % len(quota)]] += 1
        i += 1
    return tuple(widths), tuple(quota)


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Static configuration of the prediction service (hashable — part
    of every cached program's closure)."""

    max_doc_len: int = 256        # admission limit == PRNG ctr_stride
    batch_docs: int = 32          # slots per micro-batch
    width_ladder: tuple = ()      # ascending rung widths; () = 1 rung
                                  # at max_doc_len (the padded layout)
    slot_quota: tuple = ()        # slots per rung; () = all batch_docs
                                  # on the single rung
    combine: str = "weighted"     # "simple" | "weighted" | "median"
    bucketed: bool = True         # False = dispatch the padded
                                  # degenerate schedule (parity twin /
                                  # A-B baseline); the ladder still
                                  # packs, only the dispatch layout
                                  # changes — bit-identical outputs
    cache_results: bool = True    # theta/ŷ result cache on content hash
    max_cached_results: int = 4096

    # ---- robustness policy (DESIGN.md §Serving-robustness)
    max_pending: int = 0          # queue bound; 0 = unbounded.  New
                                  # submissions shed (typed) at the cap
    default_deadline_s: float = 0.0   # per-request deadline when the
                                  # caller gives none; 0 = no deadline
    rate_limit_per_s: float = 0.0     # token-bucket admission rate;
                                  # 0 = off
    rate_burst: int = 0           # bucket capacity; 0 = batch_docs
    robust_checks: bool = True    # screen model tables at (re)load and
                                  # per-chain ŷ at dispatch; False is
                                  # the checks-off A/B baseline
    auto_flush: bool = True       # False = caller-driven flush (open-
                                  # loop serving; lets a dispatcher that
                                  # fell behind exercise the queue bound)

    def __post_init__(self):
        ladder = self.width_ladder or (self.max_doc_len,)
        quota = self.slot_quota or (self.batch_docs,)
        if len(ladder) != len(quota):
            raise ValueError("width_ladder and slot_quota lengths differ")
        if list(ladder) != sorted(set(ladder)):
            raise ValueError("width_ladder must strictly ascend")
        if ladder[-1] != self.max_doc_len:
            raise ValueError("widest rung must equal max_doc_len")
        if sum(quota) != self.batch_docs or min(quota) < 1:
            raise ValueError("slot_quota must sum to batch_docs, each >=1")
        if self.max_pending and self.max_pending < self.batch_docs:
            raise ValueError("max_pending must be 0 (unbounded) or >= "
                             "batch_docs — a bound below one micro-batch "
                             "could never fill a dispatch")
        if self.rate_limit_per_s < 0 or self.default_deadline_s < 0 \
                or self.rate_burst < 0:
            raise ValueError("rate/deadline knobs must be >= 0")
        object.__setattr__(self, "width_ladder", tuple(ladder))
        object.__setattr__(self, "slot_quota", tuple(quota))

    @classmethod
    def calibrated(cls, lengths, *, max_doc_len: int = 256,
                   batch_docs: int = 32, n_buckets: int = 4,
                   token_block: int = 8, overhead_docs: float = 0.0,
                   **kw) -> "ServiceConfig":
        """Build a config whose slot layout fits a traffic sample."""
        widths, quota = calibrate_slots(
            lengths, batch_docs, max_doc_len, n_buckets=n_buckets,
            token_block=token_block, overhead_docs=overhead_docs)
        return cls(max_doc_len=max_doc_len, batch_docs=batch_docs,
                   width_ladder=widths, slot_quota=quota, **kw)


@dataclasses.dataclass
class Result:
    """One served prediction.  Per-chain values are kept so the
    combined scalar can be re-derived under any later alive mask.
    A shed/expired request resolves to a Result too (`status` in
    `SHED_STATUSES`, `yhat` = NaN, per-chain fields None) — overload is
    a typed outcome, never a KeyError."""

    req_id: int
    yhat: float              # combined ŷ under the weights AT SERVE TIME
    yhat_chains: np.ndarray  # [M] per-chain ŷ (None when shed)
    zbar: np.ndarray         # [M, T] per-chain posterior-mean θ (None
                             # when shed)
    latency_s: float
    from_cache: bool
    status: str = STATUS_OK


def _combine_yhat(rule: str, yhat, chain_weights, train_mse):
    """The ONE combine used for fresh batches (inside the compiled
    dispatch) and cache hits (host side) — `core.combine` semantics,
    alive mask = nonzero chain weight."""
    alive = (chain_weights > 0).astype(yhat.dtype)
    if rule == "weighted":
        return weighted_average(yhat, train_mse=train_mse, alive=alive)
    if rule == "median":
        return median(yhat, alive=alive)
    if rule == "simple":
        return simple_average(yhat, alive=alive)
    raise ValueError(f"unknown combine rule {rule!r}")


# ---------------------------------------------------------------- service

class SLDAPredictionService:
    """Continuous-batching prediction over a trained M-chain ensemble.

      svc = SLDAPredictionService(models, cfg, ServiceConfig.calibrated(
                lengths_sample, max_doc_len=256, batch_docs=32))
      rid = svc.submit(token_ids)          # auto-flushes at batch_docs
      svc.drain()                          # force out partial batches
      svc.result(rid).yhat

    `models` is a chain-stacked `SLDAModel` ([M, ...] leaves, e.g. from
    `train_chains`).  All dispatches run through the `ExecutionPlan`
    layer; see the module docstring for the caching/exactness story.
    """

    def __init__(self, models: SLDAModel, cfg: SLDAConfig,
                 svc: ServiceConfig, *, key=None, chain_weights=None,
                 backend: str | None = None, clock=None):
        self.models = models
        self.cfg = cfg
        self.svc = svc
        self.n_chains = int(models.eta.shape[0])
        self.backend = backend if backend is not None \
            else cfg.resolve_backend()
        self.key = key if key is not None else jax.random.PRNGKey(0)
        self.chain_weights = (jnp.ones((self.n_chains,), jnp.float32)
                              if chain_weights is None
                              else jnp.asarray(chain_weights, jnp.float32))
        self._plan_cache = {}                   # cache_key → jitted fn
        self._trace_counts = collections.Counter()   # cache_key → traces
        self._results = {}                      # req_id → Result
        # (content hash, model epoch) → (zbar, yhat): the epoch in the
        # key is what keeps a hot reload from serving stale predictions
        self._result_cache = collections.OrderedDict()
        # (req_id, np tokens, t_submit, absolute deadline or +inf)
        self._pending = collections.deque()
        self._next_id = 0
        self._batches = 0
        self._stats = collections.Counter()
        # injectable clock (VirtualClock in the chaos suite) — every
        # deadline/rate decision reads THIS, so overload behaviour is
        # replayable deterministically
        self._clock = clock if clock is not None else time.perf_counter
        self._model_epoch = 0                   # bumps on every hot swap
        self._ckpt_step = None                  # step of the live epoch
        self._health = np.zeros(self.n_chains, np.uint32)  # latched flags
        burst = svc.rate_burst or svc.batch_docs
        self._tokens = float(burst)             # token bucket, full start
        self._bucket_t = self._clock()
        if svc.robust_checks:
            self._screen_models(models, source="init")

    @property
    def chain_weights(self):
        return self._chain_weights

    @chain_weights.setter
    def chain_weights(self, w):
        """Keep a host-side mirror in sync — the dispatch-time health
        screen reads weights EVERY flush, and a device→host transfer
        per micro-batch is exactly the kind of overhead the <=5%
        checks budget can't afford."""
        self._chain_weights = w
        self._w_host = np.asarray(w)

    def _screen_models(self, models, *, source: str):
        """Latch `model_status` flags and quarantine chains whose
        TABLES are unhealthy (NaN/Inf φ̂ or η, broken φ̂ row sums,
        unusable train MSE).  Quarantine multiplies the weight by the
        alive mask, so operator-zeroed chains stay zeroed."""
        status = np.array(model_status(models))
        self._health = status
        bad = (status & MODEL_FAULTS) != 0
        if bad.any():
            self._stats["load_quarantines"] += int(bad.sum())
            self.chain_weights = self.chain_weights \
                * jnp.asarray(~bad, jnp.float32)
        return status

    def _take_token(self) -> bool:
        """Token-bucket admission: refill at `rate_limit_per_s` up to
        the burst capacity, spend one per admitted request.  Always
        True when rate limiting is off."""
        rate = self.svc.rate_limit_per_s
        if rate <= 0:
            return True
        now = self._clock()
        burst = self.svc.rate_burst or self.svc.batch_docs
        self._tokens = min(float(burst),
                           self._tokens + (now - self._bucket_t) * rate)
        self._bucket_t = now
        if self._tokens < 1.0:
            return False
        self._tokens -= 1.0
        return True

    def _shed(self, rid: int, status: str, t0: float) -> int:
        """Resolve a request to a typed shed Result (DESIGN.md
        §Serving-robustness: overload is an outcome, not an
        exception)."""
        self._results[rid] = Result(
            req_id=rid, yhat=float("nan"), yhat_chains=None, zbar=None,
            latency_s=self._clock() - t0, from_cache=False, status=status)
        self._stats[status] += 1
        return rid

    # ------------------------------------------------------------ intake

    def submit(self, tokens, *, deadline_s: float | None = None) -> int:
        """Enqueue one ragged document (int token ids, 1-D).  Returns a
        request id; auto-flushes whenever a full micro-batch is
        pending.  A content-hash repeat is served straight from the
        result cache (no slot), combined under the CURRENT weights.

        Admission order: validate (raises `InvalidDocument` — malformed
        payloads never consume a request id or a rate token), result
        cache, rate limit, queue bound.  `deadline_s` is a per-request
        latency budget from now (falls back to
        `svc.default_deadline_s`; 0/None = no deadline); a request
        whose deadline lapses before dispatch resolves to a typed
        `STATUS_EXPIRED` Result instead of occupying a slot.

        Runs inside the host span `slda.serve.submit` (argument
        `req_id`); an auto-flush is a child span."""
        with jax.profiler.TraceAnnotation("slda.serve.submit",
                                          req_id=self._next_id):
            return self._submit(tokens, deadline_s)

    def _submit(self, tokens, deadline_s):
        toks = np.asarray(tokens, np.int32).ravel()
        if toks.size < 1:
            self._stats["rejected_invalid"] += 1
            raise InvalidDocument("empty_doc", "document has no tokens")
        if toks.size > self.svc.max_doc_len:
            self._stats["rejected_invalid"] += 1
            raise InvalidDocument(
                "doc_too_long",
                f"doc length {toks.size} > max_doc_len "
                f"{self.svc.max_doc_len}")
        if toks.min() < 0 or toks.max() >= self.cfg.vocab_size:
            self._stats["rejected_invalid"] += 1
            raise InvalidDocument(
                "bad_token_id",
                f"token ids must lie in [0, {self.cfg.vocab_size}) "
                f"(got min {int(toks.min())}, max {int(toks.max())})")
        rid = self._next_id
        self._next_id += 1
        t0 = self._clock()
        if self.svc.cache_results:
            h = hashlib.blake2b(toks.tobytes(), digest_size=16).digest()
            hit = self._result_cache.get((h, self._model_epoch))
            if hit is not None:
                self._result_cache.move_to_end((h, self._model_epoch))
                zbar, yhat = hit
                comb = float(_combine_yhat(
                    self.svc.combine, jnp.asarray(yhat)[:, None],
                    self.chain_weights, self.models.train_mse)[0])
                self._results[rid] = Result(
                    req_id=rid, yhat=comb, yhat_chains=yhat, zbar=zbar,
                    latency_s=self._clock() - t0, from_cache=True)
                self._stats["cache_hits"] += 1
                return rid
        if not self._take_token():
            return self._shed(rid, STATUS_SHED_RATE, t0)
        if self.svc.max_pending \
                and len(self._pending) >= self.svc.max_pending:
            return self._shed(rid, STATUS_SHED_QUEUE, t0)
        if deadline_s is None:
            deadline_s = self.svc.default_deadline_s
        deadline = t0 + deadline_s if deadline_s else math.inf
        self._pending.append((rid, toks, t0, deadline))
        if self.svc.auto_flush:
            while len(self._pending) >= self.svc.batch_docs:
                self.flush()
        return rid

    # ----------------------------------------------------------- packing

    def _pack(self):
        """Pack pending docs into the fixed slot layout.  Two
        robustness steps run FIRST: requests whose deadline already
        lapsed are shed (`STATUS_EXPIRED`) before they can waste a
        slot, and survivors are ordered earliest-deadline-first
        (ties broken by request id, so deadline-free traffic — every
        deadline +inf — reduces to the original FIFO order).  Each doc
        then takes a free slot of the smallest rung that fits it,
        escalating to wider rungs when its own is full; docs that fit
        nowhere stay pending for the next batch.  Returns (per-rung
        doc lists, n_placed)."""
        ladder, quota = self.svc.width_ladder, self.svc.slot_quota
        now = self._clock()
        live = []
        while self._pending:
            item = self._pending.popleft()
            if item[3] < now:
                self._shed(item[0], STATUS_EXPIRED, item[2])
                continue
            live.append(item)
        live.sort(key=lambda it: (it[3], it[0]))    # EDF, FIFO fallback
        free = list(quota)
        placed = [[] for _ in ladder]
        leftover = collections.deque()
        n = 0
        for item in live:
            L = item[1].size
            rung = next(i for i, w in enumerate(ladder) if w >= L)
            slot = next((i for i in range(rung, len(ladder))
                         if free[i] > 0), None)
            if slot is None:
                leftover.append(item)
                continue
            free[slot] -= 1
            placed[slot].append(item)
            n += 1
        self._pending = leftover
        return placed, n

    def _build_schedule(self, placed):
        """Slot lists → (BucketedCorpus, slot_meta).  The micro-batch's
        ORIGINAL doc order is the rung-major slot order (real docs
        first, dummies after, per rung), so perm == identity and the
        padded twin (`bucketed=False`) sees the exact same rows —
        that's what makes the two layouts bit-comparable per slot.
        slot_meta[d] is (req_id, t_submit) or None for a dummy."""
        ladder, quota = self.svc.width_ladder, self.svc.slot_quota
        S = self.svc.max_doc_len
        meta, buckets = [], []
        tok_rows, mask_rows = [], []
        for w, q, docs in zip(ladder, quota, placed):
            bt = np.zeros((q, w), np.int32)
            bm = np.zeros((q, w), np.float32)
            for i, (rid, toks, t0, _deadline) in enumerate(docs):
                bt[i, :toks.size] = toks
                bm[i, :toks.size] = 1.0
                meta.append((rid, t0))
            meta.extend([None] * (q - len(docs)))
            buckets.append(Corpus(tokens=jnp.asarray(bt),
                                  mask=jnp.asarray(bm),
                                  y=jnp.zeros((q,), jnp.float32)))
            tok_rows.append(np.pad(bt, ((0, 0), (0, S - w))))
            mask_rows.append(np.pad(bm, ((0, 0), (0, S - w))))
        if self.svc.bucketed:
            D = self.svc.batch_docs
            perm = jnp.arange(D, dtype=jnp.int32)
            bc = BucketedCorpus(buckets=tuple(buckets), perm=perm,
                                inv_perm=perm, ctr_stride=S)
        else:
            bc = as_bucketed(Corpus(
                tokens=jnp.asarray(np.concatenate(tok_rows)),
                mask=jnp.asarray(np.concatenate(mask_rows)),
                y=jnp.zeros((self.svc.batch_docs,), jnp.float32)))
        return bc, meta

    # ---------------------------------------------------------- dispatch

    def _dispatch_fn(self, plan_key):
        """The retrace-free plan cache: one DISTINCT jitted callable
        per `ExecutionPlan.cache_key()`, created once and reused for
        every micro-batch with that signature (jit identity — a fresh
        `jax.jit` per batch would own a fresh trace cache and retrace
        every dispatch).  The Python body increments the trace counter
        — a side effect that fires per TRACE, never per compiled call —
        so `stats()['traces']` growing under steady-state traffic is a
        test failure, not a guess."""
        fn = self._plan_cache.get(plan_key)
        if fn is not None:
            return fn
        rule, counts = self.svc.combine, self._trace_counts

        def dispatch(keys, models, plan, chain_weights):
            counts[plan_key] += 1           # fires once per trace
            zb = plan.predict_zbar(keys, models)      # [M, D, T]
            with jax.named_scope("combine"):
                yhat = jax.vmap(lambda z, e: z @ e)(zb, models.eta)
                comb = _combine_yhat(rule, yhat, chain_weights,
                                     models.train_mse)
            return zb, yhat, comb

        fn = jax.jit(dispatch)
        self._plan_cache[plan_key] = fn
        return fn

    def set_sampler_mode(self, mode: str):
        """Switch the per-token draw mode for subsequent dispatches.
        The cfg is part of `ExecutionPlan.cache_key()`, so the next
        flush under the new mode allocates a DISTINCT jitted callable;
        programs compiled for the old mode stay cached (switching back
        is free).  Results are unaffected in distribution — the sparse
        two-stage draw is exact (DESIGN.md §Sparse-sampler)."""
        if mode not in ("dense", "sparse"):
            raise ValueError(f"unknown sampler_mode {mode!r}")
        self.cfg = dataclasses.replace(self.cfg, sampler_mode=mode)

    def flush(self):
        """Dispatch one micro-batch from the pending queue (no-op when
        empty).  Returns the req_ids completed by this batch (shed ids
        resolve through `result()`, not this list).

        A dispatch runs as three host spans, each with the batch index
        `batch`: `slda.serve.pack` (packing, schedule, plan and keys,
        host→device copies included; also `docs`, the documents
        placed), `slda.serve.device` (the compiled dispatch through
        `block_until_ready`) and `slda.serve.publish` (`_publish`).  An
        empty queue records none."""
        if not self._pending:
            return []
        batch = self._batches
        with jax.profiler.TraceAnnotation("slda.serve.pack",
                                          batch=batch) as span:
            placed, n = self._pack()
            span.set_metadata(docs=n)
            if n == 0:  # every pending request expired — nothing to run
                return []
            bc, meta = self._build_schedule(placed)
            plan = build_plan(bc, self.cfg, self.backend)
            fn = self._dispatch_fn(plan.cache_key())
            keys = jax.random.split(
                jax.random.fold_in(self.key, batch), self.n_chains)
        self._batches += 1
        with jax.profiler.TraceAnnotation("slda.serve.device", batch=batch):
            zb, yhat, comb = fn(keys, self.models, plan, self.chain_weights)
            jax.block_until_ready(comb)
        t_done = self._clock()
        with jax.profiler.TraceAnnotation("slda.serve.publish", batch=batch):
            return self._publish(bc, meta, n, zb, yhat, comb, t_done)

    def _publish(self, bc, meta, n, zb, yhat, comb, t_done):
        """Copy one dispatch's outputs to the host, screen them, and
        resolve its requests (results and result cache)."""
        zb, yhat, comb = np.asarray(zb), np.asarray(yhat), np.asarray(comb)
        real = [d for d, slot in enumerate(meta) if slot is not None]
        if self.svc.robust_checks and real:
            comb = self._screen_dispatch(yhat, comb, real)
        done = []
        for d, slot in enumerate(meta):
            if slot is None:
                self._stats["dummy_slots"] += 1
                continue
            rid, t0 = slot
            self._results[rid] = Result(
                req_id=rid, yhat=float(comb[d]), yhat_chains=yhat[:, d],
                zbar=zb[:, d], latency_s=t_done - t0, from_cache=False)
            done.append(rid)
            if self.svc.cache_results:
                h = hashlib.blake2b(
                    np.ascontiguousarray(
                        bc_tokens_row(bc, d)).tobytes(),
                    digest_size=16).digest()
                self._result_cache[(h, self._model_epoch)] = \
                    (zb[:, d], yhat[:, d])
                while len(self._result_cache) > self.svc.max_cached_results:
                    self._result_cache.popitem(last=False)
        self._stats["dispatches"] += 1
        self._stats["docs_dispatched"] += n
        return done

    def _screen_dispatch(self, yhat, comb, real):
        """Per-chain ŷ health screen at dispatch: a chain producing a
        non-finite prediction on any REAL slot (dummies are masked
        noise) is quarantined through the same weights path as a
        manual `drop_chain` — exact and retrace-free — and the batch
        is recombined host-side under the corrected mask, so the
        poison never reaches a caller."""
        w = self._w_host
        bad = ~np.isfinite(yhat[:, real]).all(axis=1) & (w > 0)
        if not bad.any():
            return comb
        for c in np.flatnonzero(bad):
            self._health[c] |= F_NAN_YHAT
            self.drop_chain(int(c))
            self._stats["dispatch_quarantines"] += 1
        return np.asarray(_combine_yhat(
            self.svc.combine, jnp.asarray(yhat), self.chain_weights,
            self.models.train_mse))

    def drain(self, deadline_s: float | None = None):
        """Flush until the pending queue is empty (partial batches pad
        with dummy slots).  `deadline_s` bounds the wall time spent
        draining — on timeout the remaining requests STAY pending
        (they are not shed; a later flush/drain can still serve them),
        so a shutdown storm cannot hang the caller."""
        t0 = self._clock()
        done = []
        while self._pending:
            if deadline_s is not None and self._clock() - t0 > deadline_s:
                self._stats["drain_timeouts"] += 1
                break
            done.extend(self.flush())
        return done

    # ----------------------------------------------------------- results

    def result(self, req_id: int) -> Result:
        return self._results[req_id]

    def combined(self, req_id: int) -> float:
        """Re-derive the combined ŷ for a served request under the
        CURRENT chain weights — exact under any drop/revive since the
        per-chain values never depended on other chains.  When every
        chain is dead this inherits `core.combine`'s all-dead
        fallback: unmasked combine + RuntimeWarning, never a NaN from
        a 0/0."""
        r = self._results[req_id]
        if r.status != STATUS_OK:
            raise ValueError(
                f"request {req_id} was not served (status {r.status!r})"
                " — no per-chain values to combine")
        return float(_combine_yhat(
            self.svc.combine, jnp.asarray(r.yhat_chains)[:, None],
            self.chain_weights, self.models.train_mse)[0])

    # ---------------------------------------------- ensemble maintenance

    def drop_chain(self, idx: int):
        """Serving-time straggler/failure cut — zero the chain's weight.
        Reaches every CACHED plan without retracing (weights are a jit
        argument), and is exact: chains share nothing, so the surviving
        combine equals an ensemble that never held the chain."""
        self.chain_weights = self.chain_weights.at[idx].set(0.0)

    def revive_chain(self, idx: int, weight: float = 1.0):
        """Undo a drop — the replica came back.  Exact for the same
        reason the drop is.  Also clears the chain's latched health
        flags (an operator revive is an assertion the replica is
        healthy again; the next dispatch re-screens anyway)."""
        self.chain_weights = self.chain_weights.at[idx].set(weight)
        self._health[idx] = 0

    def reload_from_checkpoint(self, ckpt_dir: str,
                               step: int | None = None) -> dict:
        """Hot model swap — epoch-versioned and atomic from the
        caller's view (DESIGN.md §Serving-robustness reload protocol):

          validate manifest → load all chains → screen tables → swap.

        Any failure before the swap (missing/torn/`BadZipFile`
        checkpoint, mislabelled manifest, chain-count mismatch, or a
        checkpoint with NO healthy chain) REJECTS the reload: the old
        models keep serving under the old epoch, and the report says
        why.  On success the model epoch bumps — which invalidates
        every result-cache entry by key, no scan needed — healthy
        chains (re)enter the ensemble and unhealthy ones are
        quarantined.  Models ride as jit ARGUMENTS with unchanged
        shapes, so a swap can never retrace."""
        t0 = self._clock()

        def _reject(reason: str) -> dict:
            self._stats["reloads_rejected"] += 1
            return {"ok": False, "reason": reason,
                    "epoch": self._model_epoch,
                    "ckpt_step": self._ckpt_step,
                    "wall_s": self._clock() - t0}

        if step is None:
            step = latest_step(ckpt_dir)
            if step is None:
                return _reject(f"no checkpoint under {ckpt_dir!r}")
        try:
            models, manifest = restore_checkpoint(
                ckpt_dir, step, self.models)
        except TORN_CHECKPOINT_ERRORS as e:
            return _reject(f"{type(e).__name__}: {e}")
        quarantined = []
        if self.svc.robust_checks:
            status = np.array(model_status(models))
            bad = (status & MODEL_FAULTS) != 0
            if bad.all():
                return _reject("all_chains_unhealthy")
            quarantined = [int(c) for c in np.flatnonzero(bad)]
            self._health = status
            alive = (~bad).astype(np.float32)
        else:
            alive = np.ones(self.n_chains, np.float32)
        # point of no return — everything below is pure assignment
        self.models = models
        self._model_epoch += 1
        self._ckpt_step = int(manifest["step"])
        self.chain_weights = jnp.asarray(alive, jnp.float32)
        self._stats["reloads_ok"] += 1
        if quarantined:
            self._stats["load_quarantines"] += len(quarantined)
        return {"ok": True, "epoch": self._model_epoch,
                "ckpt_step": self._ckpt_step,
                "quarantined_chains": quarantined,
                "wall_s": self._clock() - t0}

    # ------------------------------------------------------------- stats

    def stats(self) -> dict:
        """Counters the benchmark/tests assert on — most importantly
        `traces`: total times any cached dispatch was (re)traced.
        Steady-state traffic must not grow it."""
        sig_traces = {str(k[0]): v for k, v in self._trace_counts.items()}
        slot_total = max(self._stats["dispatches"], 1) \
            * self.svc.batch_docs
        alive = np.asarray(self.chain_weights) > 0
        return {
            "traces": int(sum(self._trace_counts.values())),
            "compiled_plans": len(self._plan_cache),
            # the active per-token draw mode — part of every plan cache
            # key (cfg is in ExecutionPlan.cache_key()), so switching it
            # allocates a DISTINCT jitted callable (test_slda_serving)
            "sampler_mode": self.cfg.sampler_mode,
            "traces_by_signature": sig_traces,
            "dispatches": int(self._stats["dispatches"]),
            "docs_dispatched": int(self._stats["docs_dispatched"]),
            "dummy_slots": int(self._stats["dummy_slots"]),
            "dummy_slot_frac": round(
                self._stats["dummy_slots"]
                / (slot_total if self._stats["dispatches"] else 1), 4),
            "result_cache_hits": int(self._stats["cache_hits"]),
            "result_cache_size": len(self._result_cache),
            "width_ladder": list(self.svc.width_ladder),
            "slot_quota": list(self.svc.slot_quota),
            "bucketed": self.svc.bucketed,
            "backend": self.backend,
            # robustness observability (ISSUE 8: queue depth, shed/
            # reject counters, model epoch, per-chain health)
            "queue_depth": len(self._pending),
            "shed_queue_full": int(self._stats[STATUS_SHED_QUEUE]),
            "shed_rate_limit": int(self._stats[STATUS_SHED_RATE]),
            "expired": int(self._stats[STATUS_EXPIRED]),
            "rejected_invalid": int(self._stats["rejected_invalid"]),
            "drain_timeouts": int(self._stats["drain_timeouts"]),
            "dispatch_quarantines": int(
                self._stats["dispatch_quarantines"]),
            "load_quarantines": int(self._stats["load_quarantines"]),
            "reloads_ok": int(self._stats["reloads_ok"]),
            "reloads_rejected": int(self._stats["reloads_rejected"]),
            "model_epoch": self._model_epoch,
            "ckpt_step": self._ckpt_step,
            "alive_chains": int(alive.sum()),
            "chain_health": [describe_status(int(s))
                             for s in self._health],
        }

    def describe(self) -> dict:
        """The serving plan, human-readable — slot layout, signature,
        and what a dispatch compiles to (`launch/dryrun.py
        --slda-serve`)."""
        dummy = [(0, np.zeros(1, np.int32), 0.0, math.inf)]
        placed = [[] for _ in self.svc.width_ladder]
        placed[0] = dummy
        bc, _ = self._build_schedule(placed)
        plan = build_plan(bc, self.cfg, self.backend)
        d = plan.describe()
        d["cache_key_signature"] = str(plan.cache_key()[0])
        d["width_ladder"] = list(self.svc.width_ladder)
        d["slot_quota"] = list(self.svc.slot_quota)
        d["combine"] = self.svc.combine
        d["chains"] = self.n_chains
        d["robustness"] = {
            "max_pending": self.svc.max_pending,
            "default_deadline_s": self.svc.default_deadline_s,
            "rate_limit_per_s": self.svc.rate_limit_per_s,
            "rate_burst": self.svc.rate_burst or self.svc.batch_docs,
            "robust_checks": self.svc.robust_checks,
            "auto_flush": self.svc.auto_flush,
            "scheduling": "earliest-deadline-first (FIFO when no "
                          "deadlines)",
            "shed_statuses": list(SHED_STATUSES),
            "model_epoch": self._model_epoch,
        }
        return d


def bc_tokens_row(bc: BucketedCorpus, d: int) -> np.ndarray:
    """Original-order row d of a schedule whose perm is the identity —
    the service's content-hash source (un-padded to the TRUE length so
    a repeat submission hashes equal regardless of its rung)."""
    o = 0
    for b in bc.buckets:
        q = b.tokens.shape[0]
        if d < o + q:
            row = np.asarray(b.tokens[d - o])
            m = np.asarray(b.mask[d - o]).astype(bool)
            return row[: int(m.sum())]
        o += q
    raise IndexError(d)
