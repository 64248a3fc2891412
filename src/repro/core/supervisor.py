"""Chain supervisor: health checks, quarantine, checkpointed restart.

The paper's central property — M chains that never communicate — is also
a fault-isolation guarantee: a NaN-poisoned, diverged, or dead chain can
be quarantined or restarted without touching any other chain, and the
ensemble prediction degrades EXACTLY (not approximately) through the
alive-masks of `core.combine` (DESIGN.md §Fault-model).  Industrial
topic-model deployments treat worker failure as routine (Zheng et al.,
Model-Parallel Inference for Big Topic Models); this layer cashes the
guarantee in:

  * **in-loop health checks** compiled into the EM scan via
    `ExecutionPlan.train_em(em_hook=...)`: per-chain NaN/Inf flags on
    η/ntw/ndt, cheap count-invariant probes (Σ ndt == Σ lengths,
    min ntw ≥ 0), and a train-MSE robust-z outlier score
    (`metrics.robust_z` — the same statistic as the out-of-band
    `ensemble_health` probe), accumulated into a per-chain uint32
    status vector with ZERO extra host syncs inside the scan and
    surfaced only at round boundaries;
  * **quarantine**: an unhealthy chain gets `alive=False`, threaded
    through every combine rule — because chains never communicate, the
    surviving sub-ensemble's prediction is bit-identical to one that
    never contained the dead chain;
  * **recovery**: bounded restart-from-checkpoint with exponential
    backoff (`checkpoint.restore_chain`), reseeding the restarted
    chain's PRNG lane (a fresh `fold_in` epoch → a distinct counter
    stream, so a transient failure is not deterministically replayed);
    when the restart budget is exhausted — or no checkpoint directory
    was given — the policy falls back to quarantine-only.

Decision table (see DESIGN.md §Fault-model for the taxonomy):

  fault class                 bits                       action
  --------------------------- -------------------------- ----------------
  NaN/Inf state               F_NAN_{ETA,NTW,NDT}        restart → quarantine
  count-invariant violation   F_NDT_SUM, F_NTW_NEG       restart → quarantine
  dead worker                 F_KILLED                   restart → quarantine
  statistical divergence      F_MSE_OUTLIER              quarantine only
  straggler                   F_STRAGGLER                flag only (serving
                                                         drops at combine)

Hard faults mean the chain's *state* is unusable — restart from the last
checkpoint is the only way to recover the lane.  A diverged-but-finite
chain is functional (dropping it is exact, restarting it would just
re-run the same posterior), and a straggler is correct, merely late.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import (TORN_CHECKPOINT_ERRORS, CheckpointManager,
                              latest_step, restore_chain)
from repro.metrics.ensemble import robust_z

from . import combine
from .plan import ExecutionPlan, build_plan, build_schedule
from .types import GibbsState, SLDAConfig, _concat_corpora, partition

# ---------------------------------------------------- per-chain status bits

F_NAN_ETA = 1 << 0       # non-finite regression weights η
F_NAN_NTW = 1 << 1       # non-finite topic-word counts
F_NAN_NDT = 1 << 2       # non-finite doc-topic counts
F_NDT_SUM = 1 << 3       # Σ ndt drifted from Σ true lengths
F_NTW_NEG = 1 << 4       # negative topic-word count
F_MSE_OUTLIER = 1 << 5   # train-MSE robust-z outlier (diverged)
F_KILLED = 1 << 6        # dead worker (reported by the fault/runtime layer)
F_STRAGGLER = 1 << 7     # late worker (flag only)

# serve-time bits (model-table screening + dispatch health — the serving
# tier's half of the taxonomy, DESIGN.md §Serving-robustness)
F_NAN_PHI = 1 << 8       # non-finite topic-word table φ̂
F_PHI_ROWSUM = 1 << 9    # φ̂ rows are not probability distributions
F_NAN_MSE = 1 << 10      # non-finite/negative train MSE (breaks weighting)
F_NAN_YHAT = 1 << 11     # non-finite served prediction at dispatch

#: state-corrupting faults — restart-from-checkpoint is worth trying
HARD_FAULTS = (F_NAN_ETA | F_NAN_NTW | F_NAN_NDT | F_NDT_SUM | F_NTW_NEG
               | F_KILLED)
#: statistical faults — the lane is functional, quarantine is exact
SOFT_FAULTS = F_MSE_OUTLIER
#: model-table faults — a chain whose exported model trips one of these
#: cannot serve; the prediction service quarantines it at (re)load
MODEL_FAULTS = F_NAN_PHI | F_PHI_ROWSUM | F_NAN_ETA | F_NAN_MSE

_BIT_NAMES = {
    F_NAN_ETA: "nan_eta", F_NAN_NTW: "nan_ntw", F_NAN_NDT: "nan_ndt",
    F_NDT_SUM: "ndt_sum", F_NTW_NEG: "ntw_neg",
    F_MSE_OUTLIER: "mse_outlier", F_KILLED: "killed",
    F_STRAGGLER: "straggler",
    F_NAN_PHI: "nan_phi", F_PHI_ROWSUM: "phi_rowsum",
    F_NAN_MSE: "nan_mse", F_NAN_YHAT: "nan_yhat",
}

_FRESH_SALT = 0x5EED      # fold_in salt of the fresh-init key lane


def describe_status(bits: int) -> list:
    """Human-readable names of the set status bits."""
    return [name for bit, name in _BIT_NAMES.items() if bits & bit]


class EnsembleHealthError(RuntimeError):
    """Raised when the alive fraction falls below
    `RecoveryPolicy.min_alive_frac` — the ensemble is no longer
    trustworthy and the operator must intervene."""


# ----------------------------------------------------------- configuration

@dataclasses.dataclass(frozen=True)
class HealthConfig:
    """What the in-scan probe checks at every EM boundary.  All checks
    are O(state) elementwise reductions — no host syncs, no collectives;
    the measured hot-path overhead is in BENCH_slda_robust.json."""

    check_nan: bool = True
    check_counts: bool = True
    check_mse: bool = True
    count_tol: float = 0.5   # counts are exact ±1 float32 adds; any
                             # drift beyond rounding is corruption
    mse_z_cut: float = 6.0   # robust z on per-chain train MSE across the
                             # ALIVE ensemble; conservative — shards
                             # differ in difficulty and quarantine of a
                             # soft fault is irreversible
    mse_rel_floor: float = 0.5   # scale floor as a fraction of the median
                                 # MSE: small ensembles with near-equal
                                 # MSEs have MAD ≈ 0, and an unfloored z
                                 # flags rounding jitter; with the floor
                                 # a chain must sit ≳(1 + cut·floor)×
                                 # the median MSE to count as diverged
    mse_warmup: int = 8      # EM boundaries before the MSE probe arms:
                             # burn-in MSEs swing wildly chain-to-chain
                             # and the latched status would quarantine
                             # chains for transients that converge away

    @property
    def enabled(self) -> bool:
        return self.check_nan or self.check_counts or self.check_mse


@dataclasses.dataclass(frozen=True)
class RecoveryPolicy:
    """What to do about an unhealthy chain (see the module decision
    table).  Restarts are per-chain and bounded; exhausting the budget
    falls back to quarantine-only, which is always exact."""

    max_restarts: int = 2
    backoff_base: float = 0.0    # seconds; sleep backoff_base · 2^k
                                 # before the k-th restart (0 = none —
                                 # in-process restarts need no settle
                                 # time; real cluster relaunches do)
    min_alive_frac: float = 0.25  # below this, raise EnsembleHealthError

    def backoff_s(self, n_prior_restarts: int) -> float:
        return self.backoff_base * (2.0 ** n_prior_restarts)


# --------------------------------------------------------- the in-scan probe

def _flag(bad, flag):
    return jnp.where(bad, jnp.uint32(flag), jnp.uint32(0))


def chain_status(plan: ExecutionPlan, state: GibbsState,
                 health: HealthConfig, alive, it=None) -> jnp.ndarray:
    """Per-chain status bits [M] uint32 from the chain-batched state —
    pure jnp, safe inside the EM scan.  `alive` [M] float masks which
    chains participate in the cross-chain MSE statistic (a quarantined
    lane keeps running garbage and must not skew the median); `it`, when
    given (traced EM-boundary index), arms the MSE probe only after
    `health.mse_warmup` boundaries."""
    bc = plan.corpus
    m = state.eta.shape[0]
    status = jnp.zeros((m,), jnp.uint32)
    if health.check_nan:
        fin = lambda x: jnp.isfinite(x).reshape(m, -1).all(axis=-1)
        status |= _flag(~fin(state.eta), F_NAN_ETA)
        status |= _flag(~fin(state.ntw), F_NAN_NTW)
        status |= _flag(~fin(state.ndt), F_NAN_NDT)
    if health.check_counts:
        tokens = bc.lengths().sum(-1)                    # [M] true tokens
        ndt_sum = state.ndt.reshape(m, -1).sum(-1)
        # NaN-poisoned counts make the comparison False → flag fires too
        ok_sum = jnp.abs(ndt_sum - tokens) <= health.count_tol
        status |= _flag(~ok_sum, F_NDT_SUM)
        ntw_min = state.ntw.reshape(m, -1).min(-1)
        status |= _flag(~(ntw_min >= -health.count_tol), F_NTW_NEG)
    if health.check_mse and m >= 3:
        lengths = jnp.maximum(bc.lengths(), 1.0)
        yhat = jnp.einsum("mdt,mt->md", state.ndt / lengths[..., None],
                          state.eta)
        mse = jnp.mean((yhat - bc.y) ** 2, axis=-1)
        z = robust_z(mse, valid=alive, rel_floor=health.mse_rel_floor)
        outlier = z >= health.mse_z_cut
        if it is not None:
            outlier = outlier & (jnp.asarray(it) >= health.mse_warmup)
        status |= _flag(outlier, F_MSE_OUTLIER)
    return status


def model_status(models, *, rowsum_tol: float = 1e-3) -> jnp.ndarray:
    """Per-chain status bits [M] uint32 screening an exported
    `SLDAModel` (chain-stacked leaves) — the serve-time twin of
    `chain_status`, run by the prediction service at model (re)load.
    Pure jnp, cheap (O(model) elementwise reductions):

      * NaN/Inf in φ̂ or η̂ (`F_NAN_PHI` / `F_NAN_ETA`),
      * φ̂ count invariants: every topic row is a probability
        distribution — non-negative, Σ_w φ̂[t, w] ≈ 1 (`F_PHI_ROWSUM`;
        a NaN-poisoned row also fails the comparison, same trick as the
        in-scan count probes),
      * non-finite or negative train MSE (`F_NAN_MSE` — it is the
        Weighted Average weight, so corruption here skews every
        combine, not just one chain's own prediction).

    A chain with any `MODEL_FAULTS` bit cannot serve; quarantining it
    at load is EXACT for the usual communication-free reason."""
    m = models.eta.shape[0]
    status = jnp.zeros((m,), jnp.uint32)
    fin = lambda x: jnp.isfinite(x).reshape(m, -1).all(axis=-1)
    status |= _flag(~fin(models.eta), F_NAN_ETA)
    status |= _flag(~fin(models.phi), F_NAN_PHI)
    rowsum = models.phi.sum(-1)                         # [M, T]
    rows_ok = (jnp.abs(rowsum - 1.0) <= rowsum_tol).all(-1)
    nonneg = (models.phi.reshape(m, -1).min(-1) >= -rowsum_tol)
    status |= _flag(~(rows_ok & nonneg), F_PHI_ROWSUM)
    mse_ok = jnp.isfinite(models.train_mse) & (models.train_mse >= 0.0)
    status |= _flag(~mse_ok, F_NAN_MSE)
    return status


# -------------------------------------------------------------- supervisor

@dataclasses.dataclass
class SupervisorReport:
    """What a supervised run observed: the final alive mask (feed it to
    the combine rules), latched per-chain status bits, restart counts,
    and a per-round event history."""

    alive: np.ndarray          # [M] bool
    status: np.ndarray         # [M] uint32, OR of every round
    restarts: np.ndarray       # [M] int32
    rounds: int
    history: list
    yhat_chains: np.ndarray = None   # [M, D_test], set by supervised_run

    def alive_mask(self) -> jnp.ndarray:
        return jnp.asarray(self.alive, jnp.float32)

    def quarantined(self) -> list:
        return [int(c) for c in np.nonzero(~self.alive)[0]]


class ChainSupervisor:
    """Wraps the chain-batched EM loop with health checks, quarantine,
    and checkpointed restart (module docstring).  Training is split into
    ROUNDS of `round_iters` EM iterations; inside a round everything is
    one compiled scan (health flags accumulate on-device), and rounds
    are the only points where the host reads the [M] status vector,
    takes a checkpoint, and applies the recovery policy.

    `fault_hook(state, it) -> (state, bits)` is the deterministic
    fault-injection attachment point (`repro.testing.faults`) — it runs
    inside the scan BEFORE the health probe, so an injected fault at
    boundary `it` is detectable at that same boundary."""

    def __init__(self, shards, cfg: SLDAConfig, *, health=None,
                 recovery=None, ckpt_dir=None, round_iters=None,
                 fault_hook=None, backend=None, keep_checkpoints=2):
        self.cfg = cfg
        self.health = health or HealthConfig()
        self.recovery = recovery or RecoveryPolicy()
        self.ckpt_dir = ckpt_dir
        self.plan = build_plan(shards, cfg, backend)
        assert self.plan.n_chains is not None, \
            "supervisor wants a chain-sharded schedule ([M, D/M, ...])"
        # default: ONE round — pure in-scan checking, no mid-train host
        # sync; checkpointed restart needs round_iters (and ckpt_dir)
        r = cfg.n_iters if round_iters is None else max(1, round_iters)
        n_full, rem = divmod(cfg.n_iters, r)
        self._round_sizes = [r] * n_full + ([rem] if rem else [])
        self._manager = (CheckpointManager(ckpt_dir, interval=1,
                                           keep=keep_checkpoints)
                         if ckpt_dir is not None else None)
        self._fault_hook = fault_hook
        self._init = jax.jit(lambda p, k: p.init_states(k))
        self._run_round = jax.jit(self._round_fn)
        #: times the round function was TRACED (not called) — a Python
        #: side effect inside the traced body, so steady-state rounds
        #: leave it untouched and an elastic repack can assert "zero
        #: retraces" by watching this stay constant (the same trick the
        #: serving plan cache uses)
        self.round_traces = 0

    # ---- one compiled round: EM scan with the composed hook inside
    def _round_fn(self, plan, keys, state, alive, it0):
        health, fault_hook = self.health, self._fault_hook
        self.round_traces += 1          # trace-time only — see __init__

        def hook(st, it, status):
            bits = jnp.zeros_like(status)
            if fault_hook is not None:
                st, fb = fault_hook(st, it)
                bits = bits | fb.astype(jnp.uint32)
            if health.enabled:
                bits = bits | chain_status(plan, st, health, alive, it)
            return st, status | bits

        status0 = jnp.zeros((alive.shape[0],), jnp.uint32)
        return plan.train_em(keys, state, em_hook=hook, status0=status0,
                             it_offset=it0)

    def _fold_keys(self, base, epoch, rnd):
        """Per-round per-chain keys: fold the chain's RESTART EPOCH in
        first, then the round index — a restarted chain's lane moves to
        a distinct counter stream and never deterministically replays
        the sweeps that led to the failure.

        `rnd` may be a scalar (every chain at the same logical round —
        the supervisor's wall-aligned loop) or an [M] array of PER-CHAIN
        round indices — the elastic runner's catch-up path, where a
        chain restored after device loss replays ITS OWN round-r stream
        while the survivors advance; fold_in(k, r) bits are identical
        either way, so the two cases are bitwise-interchangeable."""
        m = base.shape[0]
        rnd_arr = jnp.broadcast_to(jnp.asarray(rnd, jnp.int32), (m,))
        return jax.vmap(lambda k, e, r: jax.random.fold_in(
            jax.random.fold_in(k, e), r))(base, jnp.asarray(epoch), rnd_arr)

    def _restart_chain(self, state, c, base, epoch, events):
        """Restore chain c alone from the latest checkpoint; a corrupt or
        truncated chain file is fault-isolated to a fresh re-init of that
        one lane (the `restore_elastic` contract, per chain)."""
        step = (latest_step(self.ckpt_dir)
                if self.ckpt_dir is not None else None)
        tmpl = jax.tree.map(lambda x: x[c], state)
        chain_state, action = None, None
        if step is not None:
            try:
                chain_state = restore_chain(self.ckpt_dir, step, c, tmpl)
                action = f"restart_from_step_{step}"
            except TORN_CHECKPOINT_ERRORS as e:  # corrupt file isolation
                events.append({"chain": c, "action": "checkpoint_corrupt",
                               "error": repr(e)})
        if chain_state is None:
            keys = jax.vmap(lambda k, e: jax.random.fold_in(
                k, _FRESH_SALT + e))(base, jnp.asarray(epoch))
            fresh, _ = self._init(self.plan, keys)
            chain_state = jax.tree.map(lambda x: x[c], fresh)
            action = "restart_fresh_init"
        events.append({"chain": c, "action": action})
        return jax.tree.map(lambda x, xc: x.at[c].set(xc), state,
                            chain_state)

    # ---- reusable pieces (the elastic runtime drives these directly) --

    def make_round_plan(self, r_iters: int) -> ExecutionPlan:
        """A plan for one round of `r_iters` EM iterations.  Same corpus
        and backend → same jit cache entry for every same-sized round."""
        return ExecutionPlan(
            corpus=self.plan.corpus,
            cfg=dataclasses.replace(self.cfg, n_iters=r_iters),
            backend=self.plan.backend)

    def run_round(self, round_plan, keys, state, alive, boundary_off):
        """One compiled round; returns (state, status [M] uint32 on
        host).  The ONLY host sync per round is the status read."""
        state, status = self._run_round(
            round_plan, keys, state, jnp.asarray(alive, jnp.float32),
            boundary_off)
        return state, np.asarray(jax.device_get(status), np.uint32)

    def _apply_recovery(self, state, status_np, *, alive, epoch, restarts,
                        grace, base, events):
        """Apply the recovery policy to one round's status vector.
        Mutates the host-side bookkeeping arrays (alive/epoch/restarts/
        grace) in place and returns the possibly-patched state; the
        caller owns the per-round grace decrement."""
        recovery = self.recovery
        for c in range(len(status_np)):
            bits = int(status_np[c])
            if grace[c] > 0:
                # a chain restarted from a checkpoint lags the
                # ensemble by up to one round — its worse-but-
                # converging MSE is expected, not divergence
                bits &= ~SOFT_FAULTS
            if not alive[c] or bits == 0 or not (bits & ~F_STRAGGLER):
                continue
            restartable = (bool(bits & HARD_FAULTS)
                           and restarts[c] < recovery.max_restarts
                           and self._manager is not None)
            if restartable:
                wait = recovery.backoff_s(int(restarts[c]))
                if wait > 0:
                    time.sleep(wait)
                state = self._restart_chain(state, c, base, epoch, events)
                restarts[c] += 1
                epoch[c] += 1
                grace[c] = 2    # caller decrements → one full round
            else:
                alive[c] = False
                events.append({"chain": c, "action": "quarantine",
                               "status": describe_status(bits)})
        return state

    def _check_min_alive(self, alive, latched):
        if alive.mean() < self.recovery.min_alive_frac:
            raise EnsembleHealthError(
                f"only {int(alive.sum())}/{len(alive)} chains alive "
                f"(min_alive_frac={self.recovery.min_alive_frac}); "
                f"latched status: "
                f"{[describe_status(int(s)) for s in latched]}")

    def train(self, keys):
        """Supervised chain-batched training from per-chain keys [M].
        Returns (GibbsState, SLDAModel, SupervisorReport) — state/models
        as `ExecutionPlan.train`, plus the report whose `alive` mask the
        caller MUST thread into the combine (quarantined lanes contain
        garbage by design)."""
        plan, recovery = self.plan, self.recovery
        m = plan.n_chains
        ks = jax.vmap(jax.random.split)(keys)
        state, z_fill = self._init(plan, ks[:, 0])
        base = ks[:, 1]
        alive = np.ones(m, bool)
        epoch = np.zeros(m, np.int32)
        restarts = np.zeros(m, np.int32)
        grace = np.zeros(m, np.int32)   # rounds of soft-fault amnesty a
                                        # restarted chain gets while it
                                        # catches up to the ensemble
        latched = np.zeros(m, np.uint32)
        history = []
        it_done, boundary_off = 0, 0
        for rnd, r_iters in enumerate(self._round_sizes):
            if self._manager is not None:
                self._manager.maybe_save(it_done, state)
            round_plan = self.make_round_plan(r_iters)
            state, status_np = self.run_round(
                round_plan, self._fold_keys(base, epoch, rnd), state,
                alive, boundary_off)
            events = []
            state = self._apply_recovery(
                state, status_np, alive=alive, epoch=epoch,
                restarts=restarts, grace=grace, base=base, events=events)
            grace = np.maximum(grace - 1, 0)
            latched |= status_np
            history.append({"round": rnd, "em_iters_done": it_done + r_iters,
                            "status": [int(s) for s in status_np],
                            "events": events})
            self._check_min_alive(alive, latched)
            boundary_off += round_plan.n_boundaries()
            it_done += r_iters
        models = plan._export(state)
        state = GibbsState(z=plan.corpus.merge_padded(state.z, z_fill),
                           ndt=state.ndt, ntw=state.ntw, nt=state.nt,
                           eta=state.eta)
        report = SupervisorReport(alive=alive, status=latched,
                                  restarts=restarts,
                                  rounds=len(self._round_sizes),
                                  history=history)
        return state, models, report


# --------------------------------------------- supervised end-to-end runs

def supervised_run_average(key, train, test, cfg: SLDAConfig, m: int, *,
                           rule: str = "weighted", health=None,
                           recovery=None, ckpt_dir=None, round_iters=None,
                           fault_hook=None):
    """The fault-tolerant form of `core.parallel.run_*_average`: train M
    chains under the supervisor, predict with every chain, and combine
    with the supervisor's alive mask — a quarantined chain can never
    contaminate ŷ (its predictions are excluded EXACTLY by
    `core.combine`).  Returns (ŷ [D_test], SupervisorReport); the
    per-chain test predictions ride along as `report.yhat_chains`."""
    from .parallel import (_combine_weighted, _predict_chains_jit)
    k1, k2 = jax.random.split(key)
    shards = build_schedule(partition(train, m), cfg)
    sup = ChainSupervisor(shards, cfg, health=health, recovery=recovery,
                          ckpt_dir=ckpt_dir, round_iters=round_iters,
                          fault_hook=fault_hook)
    _, models, report = sup.train(jax.random.split(k1, m))
    alive = report.alive_mask()
    if rule == "weighted" and cfg.fuse_weighted_predict:
        both = _concat_corpora(test, train)
        yhat = _predict_chains_jit(k2, models, build_schedule(both, cfg),
                                   cfg)
        yhat_te, yhat_tr = yhat[:, :test.n_docs], yhat[:, test.n_docs:]
    else:
        yhat_te = _predict_chains_jit(k2, models,
                                      build_schedule(test, cfg), cfg)
        yhat_tr = None
    report.yhat_chains = np.asarray(jax.device_get(yhat_te))
    if rule == "simple":
        return combine.simple_average(yhat_te, alive=alive), report
    if rule == "median":
        return combine.median(yhat_te, alive=alive), report
    if rule == "weighted":
        if yhat_tr is None:
            k3 = jax.random.fold_in(k2, 1)
            yhat_tr = _predict_chains_jit(k3, models,
                                          build_schedule(train, cfg), cfg)
        return _combine_weighted(yhat_te, yhat_tr, train.y, cfg,
                                 alive), report
    raise ValueError(rule)
