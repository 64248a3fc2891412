"""Elastic preemption-tolerant ensemble training (DESIGN.md
§Elastic-training).

The paper's communication-free property makes chain↔device placement
pure scheduling metadata: a chain's Gibbs stream depends only on its own
shard, its own fold_in key lane, and its own state — never on WHERE it
runs or who its neighbours are.  This module cashes that in as
elasticity, the thing distributed-LDA systems pay synchronization
protocols for:

  * **dynamic placement** — `DevicePool` is a membership view (ordered
    device ids + an epoch bumped on every change) and
    `compute_placement` deterministically packs the M chains onto it in
    balanced contiguous groups.  Placement is recomputed at EM-round
    boundaries only, and it rides OUTSIDE the compiled round (the jit
    cache is keyed on `(bucket_signature, cfg, backend)` — no placement
    anywhere in it), so a repack after device loss causes ZERO retraces
    and survivors' streams are bit-identical to a run launched with the
    surviving layout from the start.

  * **per-chain logical progress** — each chain's round keys fold its
    OWN round counter (`ChainSupervisor._fold_keys` with an [M] round
    vector), so one compiled [M]-wide round can serve chains sitting at
    different logical rounds: a chain restored after device loss replays
    its round-s stream while survivors advance through round r.  The
    catch-up loop then freezes finished chains via a selective merge
    (`jnp.where` on an active mask) until every alive chain has run
    exactly R rounds — making the final ensemble bitwise-equal to an
    undisturbed run, device loss or not.

  * **round deadlines / stragglers** — per-device soft barriers on the
    chaos-suite `VirtualClock`: a device whose round exceeds
    `deadline_s` gets its chains flagged `F_STRAGGLER` (correct, merely
    late — flag only), `straggle_rounds` consecutive misses evict the
    device from the pool (its chains repack, state intact — slow is not
    dead), and `speculative_replace` optionally re-places the slowest
    device's chains onto the least-loaded on-time device at the first
    miss.

  * **async crash-consistent checkpointing** — `AsyncCheckpointManager`
    snapshots to host at the boundary and publishes in a background
    thread through the same atomic rename protocol; its bounded-
    staleness guarantee (a save is only accepted once the previous one
    is durable) means resume after preemption loses at most ONE EM
    round.  SIGTERM (or a deterministic "preempt" `ElasticEvent`) is
    latched by `PreemptionSignal` and honoured at the next boundary:
    flush, final synchronous save with the full host bookkeeping
    (per-chain progress/alive/epoch/restarts + wall round) in the
    manifest, exit resumable.

Fault semantics at the pool level (the chain-level taxonomy is
`core.supervisor`'s): a LOST device's chains restore from the last
durable checkpoint (no PRNG-epoch bump — the chain state was healthy,
the environment failed, and exact replay is what makes recovery exact);
with no checkpoint directory they are quarantined, which is exact for
the usual communication-free reason.  A SLOW device's chains are never
restored — they are correct, and moving them is free because state is
placement-invariant.
"""
from __future__ import annotations

import dataclasses
import signal as _signal

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import (TORN_CHECKPOINT_ERRORS, AsyncCheckpointManager,
                              CheckpointManager, read_manifest,
                              restore_chain, restore_elastic,
                              save_checkpoint)
from repro.core.supervisor import ChainSupervisor, F_KILLED, F_STRAGGLER
from repro.core.types import GibbsState, SLDAConfig, partition
from repro.core.plan import build_schedule
from repro.testing.faults import ElasticEvent, VirtualClock

# ----------------------------------------------------------- membership view


class DevicePool:
    """Ordered device membership + an epoch bumped on every change.
    The pool is a VIEW — it holds ids (ints or strings), not device
    handles; the compiled round never sees it."""

    def __init__(self, devices):
        if isinstance(devices, int):
            devices = list(range(devices))
        if not devices:
            raise ValueError("device pool cannot start empty")
        self._ids = list(devices)
        self.epoch = 0
        self.history = [("init", tuple(self._ids))]

    @property
    def ids(self):
        return tuple(self._ids)

    def __len__(self):
        return len(self._ids)

    def __contains__(self, dev):
        return dev in self._ids

    def lose(self, dev):
        if dev not in self._ids:
            return False
        if len(self._ids) == 1:
            raise RuntimeError(
                f"device {dev!r} is the last pool member — losing it "
                "leaves nowhere to run; treat as total failure upstream")
        self._ids.remove(dev)
        self.epoch += 1
        self.history.append(("lose", dev))
        return True

    def join(self, dev):
        if dev in self._ids:
            return False
        self._ids.append(dev)
        self.epoch += 1
        self.history.append(("join", dev))
        return True


def compute_placement(chain_ids, devices) -> dict:
    """Deterministic balanced placement: chains (sorted) split into
    len(devices) contiguous groups, earlier devices taking the +1
    remainders.  Pure function of (chain_ids, device order) — the same
    membership view always yields the same placement, which is what
    makes a repack reproducible from the event log alone."""
    devices = list(devices)
    if not devices:
        raise ValueError("cannot place chains on an empty pool")
    chains = sorted(int(c) for c in chain_ids)
    n, k = len(chains), len(devices)
    per, rem = divmod(n, k)
    out, i = {}, 0
    for j, dev in enumerate(devices):
        take = per + (1 if j < rem else 0)
        out[dev] = tuple(chains[i:i + take])
        i += take
    return out


# -------------------------------------------------------- preemption signal


class PreemptionSignal:
    """Latched preemption notice.  `install()` hooks SIGTERM (the
    cloud-preemption convention) so an external notice and a
    deterministic chaos `ElasticEvent("preempt", ...)` flow through the
    same flag; the runner honours it at the next round boundary."""

    def __init__(self):
        self.triggered = False
        self._prev = None

    def set(self, *_args):
        self.triggered = True

    def clear(self):
        self.triggered = False

    def install(self):
        self._prev = _signal.signal(_signal.SIGTERM, self.set)
        return self

    def uninstall(self):
        if self._prev is not None:
            _signal.signal(_signal.SIGTERM, self._prev)
            self._prev = None


# ------------------------------------------------------------- configuration


@dataclasses.dataclass(frozen=True)
class ElasticConfig:
    """Pool-level runtime policy (chain-level health/recovery stay in
    `HealthConfig`/`RecoveryPolicy`)."""

    round_iters: int = 2         # EM iters per round; must divide
                                 # cfg.n_iters — every round is the SAME
                                 # compiled computation, and a chain
                                 # replaying round s after restore must
                                 # replay the SAME round size it first ran
    async_ckpt: bool = True      # AsyncCheckpointManager vs synchronous
    ckpt_every: int = 1          # checkpoint every k wall rounds; the
                                 # bounded-staleness guarantee scales
                                 # with it — resume/recovery loses at
                                 # most `ckpt_every` EM rounds
    keep_checkpoints: int = 3
    catch_up: bool = True        # run extra wall rounds until every alive
                                 # chain reaches R logical rounds (exact
                                 # recovery); False = fixed wall budget,
                                 # laggards ship stale state (reported)
    device_round_s: float = 1.0  # simulated seconds one device takes per
                                 # round (the VirtualClock's unit of work)
    deadline_s: float | None = None   # round deadline; None disables the
                                      # straggler machinery entirely
    straggle_rounds: int = 2     # consecutive deadline misses before the
                                 # device is evicted from the pool
    speculative_replace: bool = False  # move the slowest device's chains
                                       # to the least-loaded on-time
                                       # device at the FIRST miss


@dataclasses.dataclass
class ElasticReport:
    """What an elastic run observed — the supervisor report's pool-level
    twin.  `alive`/`status`/`restarts` as in `SupervisorReport`;
    `progress` is each chain's completed logical rounds (== R everywhere
    on a clean or fully-caught-up run)."""

    alive: np.ndarray
    status: np.ndarray
    restarts: np.ndarray
    progress: np.ndarray
    wall_rounds: int
    logical_rounds: int
    history: list
    pool_history: list
    placements: list
    preempted: bool = False
    resume_round: int | None = None
    sim_seconds: float = 0.0
    round_traces: int = 0
    yhat_chains: np.ndarray = None

    def alive_mask(self) -> jnp.ndarray:
        return jnp.asarray(self.alive, jnp.float32)

    def quarantined(self) -> list:
        return [int(c) for c in np.nonzero(~self.alive)[0]]

    def laggards(self) -> list:
        return [int(c) for c in
                np.nonzero(self.alive & (self.progress
                                         < self.logical_rounds))[0]]


# ----------------------------------------------------------------- runner


class ElasticRunner:
    """Drives `ChainSupervisor.run_round` under a dynamic device pool.

    One process simulates the pool (this repo's single-host idiom —
    `launch/slda_parallel.py` holds the real multi-device shard_map):
    every wall round executes the full [M]-wide compiled round once and
    a selective merge keeps only the ACTIVE chains' new state, so chains
    at different logical rounds, on any placement, share one jit cache
    entry.  All elasticity — membership, placement, deadlines,
    restore — is host metadata between compiled calls.
    """

    def __init__(self, shards, cfg: SLDAConfig, *, devices=2,
                 elastic: ElasticConfig | None = None, health=None,
                 recovery=None, ckpt_dir=None, fault_hook=None,
                 backend=None, clock: VirtualClock | None = None,
                 events=(), preemption: PreemptionSignal | None = None):
        self.elastic = elastic or ElasticConfig()
        if cfg.n_iters % self.elastic.round_iters:
            raise ValueError(
                f"round_iters={self.elastic.round_iters} must divide "
                f"cfg.n_iters={cfg.n_iters}: elastic replay needs every "
                "round to be the same compiled computation")
        self.cfg = cfg
        self.ckpt_dir = ckpt_dir
        self.sup = ChainSupervisor(
            shards, cfg, health=health, recovery=recovery,
            ckpt_dir=ckpt_dir, round_iters=self.elastic.round_iters,
            fault_hook=fault_hook, backend=backend,
            keep_checkpoints=self.elastic.keep_checkpoints)
        self.pool = DevicePool(devices)
        self.clock = clock or VirtualClock()
        self.events = sorted(events, key=lambda e: e.at_round)
        self.preemption = preemption or PreemptionSignal()
        if ckpt_dir is not None:
            mgr_cls = (AsyncCheckpointManager if self.elastic.async_ckpt
                       else CheckpointManager)
            self.manager = mgr_cls(ckpt_dir,
                                   interval=self.elastic.ckpt_every,
                                   keep=self.elastic.keep_checkpoints)
        else:
            self.manager = None
        # selective merge: keep `new` only where the chain was active
        # this wall round — a frozen chain's state passes through
        # bit-identically (jnp.where copies bits, it does not recompute)
        self._merge = jax.jit(lambda new, old, act: jax.tree.map(
            lambda n, o: jnp.where(
                act.reshape((act.shape[0],) + (1,) * (n.ndim - 1)), n, o),
            new, old))

    # ---- host bookkeeping helpers -------------------------------------

    def _extra(self, bk, wall):
        return {"progress": [int(x) for x in bk["progress"]],
                "alive": [bool(x) for x in bk["alive"]],
                "epoch": [int(x) for x in bk["epoch"]],
                "restarts": [int(x) for x in bk["restarts"]],
                "wall_round": int(wall),
                "pool": [int(d) for d in self.pool.ids]}

    def _restore_victim(self, state, c, bk, events):
        """Device-loss recovery for chain `c`: restore its slice from
        the last DURABLE checkpoint and rewind its logical progress to
        the checkpoint's recorded value — NO PRNG-epoch bump, because
        the chain state was healthy (the environment failed) and exact
        replay of rounds s..R is precisely what makes the recovered
        chain bitwise-equal to one that never moved.  A torn/corrupt
        chain file falls back to fresh init WITH an epoch bump (that
        chain genuinely lost history)."""
        durable = self.manager.latest_durable()
        if durable is None:
            bk["alive"][c] = False
            bk["latched"][c] |= F_KILLED
            events.append({"chain": c, "action": "quarantine_no_checkpoint"})
            return state
        tmpl = jax.tree.map(lambda x: x[c], state)
        try:
            chain_state = restore_chain(self.ckpt_dir, durable, c, tmpl)
            extra = read_manifest(self.ckpt_dir, durable).get("extra", {})
            rewind = int(extra.get("progress", [0] * (c + 1))[c])
            events.append({"chain": c, "action":
                           f"restore_step_{durable}_progress_{rewind}"})
        except TORN_CHECKPOINT_ERRORS as e:  # torn file is fault-isolated
            bk["epoch"][c] += 1
            rewind = 0
            keys = jax.vmap(
                lambda k, eo: jax.random.fold_in(k, 0x5EED + eo))(
                    self._base, jnp.asarray(bk["epoch"]))
            fresh, _ = self.sup._init(self.sup.plan, keys)
            chain_state = jax.tree.map(lambda x: x[c], fresh)
            events.append({"chain": c, "action": "restore_corrupt_fresh",
                           "error": repr(e)})
        bk["progress"][c] = rewind
        # amnesty while it replays: its MSE is legitimately behind the
        # ensemble until it catches back up
        bk["grace"][c] = int(max(bk["progress"]) - rewind) + 1
        return jax.tree.map(lambda x, xc: x.at[c].set(xc), state,
                            chain_state)

    def _repack(self, bk, placements, why):
        alive_chains = [c for c in range(len(bk["alive"]))
                        if bk["alive"][c]]
        self.placement = compute_placement(alive_chains, self.pool.ids)
        placements.append({"why": why, "pool_epoch": self.pool.epoch,
                           "placement": {str(d): list(cs) for d, cs
                                         in self.placement.items()}})

    def _apply_event(self, ev, state, bk, events, placements, straggles):
        if ev.kind == "preempt":
            self.preemption.set()
            events.append({"action": "preempt_notice"})
        elif ev.kind == "device_loss":
            if not self.pool.lose(ev.device):
                events.append({"action": "device_loss_noop",
                               "device": ev.device})
                return state
            victims = [c for c in self.placement.get(ev.device, ())
                       if bk["alive"][c]]
            events.append({"action": "device_loss", "device": ev.device,
                           "victims": victims})
            if self.manager is not None:
                # settle the in-flight async write first: the snapshot
                # for the last completed round is already taken, so the
                # wait costs nothing and every victim then restores from
                # the SAME (newest) step — deterministic recovery that
                # loses zero completed rounds
                self.manager.flush()
            for c in victims:
                if self.manager is None:
                    bk["alive"][c] = False
                    bk["latched"][c] |= F_KILLED
                    events.append({"chain": c,
                                   "action": "quarantine_no_checkpoint"})
                else:
                    state = self._restore_victim(state, c, bk, events)
            self._repack(bk, placements, f"device_loss:{ev.device}")
        elif ev.kind == "device_join":
            if self.pool.join(ev.device):
                events.append({"action": "device_join",
                               "device": ev.device})
                self._repack(bk, placements, f"device_join:{ev.device}")
        elif ev.kind == "straggle":
            straggles.append([ev.device, float(ev.delay_s),
                              int(ev.rounds)])
            events.append({"action": "straggle_start",
                           "device": ev.device, "delay_s": ev.delay_s,
                           "rounds": ev.rounds})
        else:
            raise ValueError(f"unknown elastic event kind {ev.kind!r}")
        return state

    def _round_clock(self, bk, events, placements, straggles, late):
        """Advance the virtual clock by this wall round's slowest device
        and apply the straggler policy (flag → escalate → optionally
        re-place).  Returns the per-device finish times."""
        el = self.elastic
        finish = {}
        for dev in self.pool.ids:
            delay = sum(s[1] for s in straggles
                        if s[0] == dev and s[2] > 0)
            finish[dev] = el.device_round_s + delay
        for s in straggles:
            if s[2] > 0:
                s[2] -= 1
        self.clock.advance(max(finish.values()) if finish else 0.0)
        if el.deadline_s is None:
            return finish
        on_time = [d for d in self.pool.ids
                   if finish[d] <= el.deadline_s]
        for dev in list(self.pool.ids):
            if finish[dev] <= el.deadline_s:
                late[dev] = 0
                continue
            late[dev] = late.get(dev, 0) + 1
            for c in self.placement.get(dev, ()):
                bk["latched"][c] |= F_STRAGGLER
            events.append({"action": "deadline_miss", "device": dev,
                           "finish_s": finish[dev],
                           "consecutive": late[dev]})
            if late[dev] >= el.straggle_rounds and len(self.pool) > 1:
                # slow is not dead: evict the DEVICE, keep the chains —
                # their state is correct and placement-invariant
                self.pool.lose(dev)
                events.append({"action": "straggler_evicted",
                               "device": dev})
                self._repack(bk, placements, f"straggler:{dev}")
            elif el.speculative_replace and on_time:
                target = min(on_time,
                             key=lambda d: len(self.placement.get(d, ())))
                moved = self.placement.get(dev, ())
                if moved and target != dev:
                    self.placement[target] = tuple(
                        sorted(self.placement.get(target, ()) + moved))
                    self.placement[dev] = ()
                    events.append({"action": "speculative_replace",
                                   "device": dev, "target": target,
                                   "chains": list(moved)})
                    placements.append(
                        {"why": f"speculative:{dev}->{target}",
                         "pool_epoch": self.pool.epoch,
                         "placement": {str(d): list(cs) for d, cs
                                       in self.placement.items()}})
        return finish

    def _drain(self, state, bk, wall, events):
        """Graceful preemption drain: flush the in-flight async write,
        publish a final synchronous checkpoint carrying the complete
        host bookkeeping, and leave the run resumable.  Total loss on
        resume: the (at most one) round that was in flight when the
        notice arrived."""
        if self.manager is not None:
            # the drain save is unconditional (ignores ckpt_every) and
            # synchronous: the process is about to die and this state is
            # the cheapest round to not lose
            self.manager.flush()
            save_checkpoint(self.ckpt_dir, wall,
                            jax.tree.map(lambda x: np.array(
                                jax.device_get(x)), state),
                            extra=self._extra(bk, wall))
            self.manager._gc()
        events.append({"action": "preempt_drain", "wall_round": wall,
                       "durable": (self.manager.latest_durable()
                                   if self.manager else None)})

    # ---- the wall-round loop ------------------------------------------

    def train(self, root_key, *, resume: bool = False):
        """Train M chains elastically from a single root key (per-chain
        lanes are `fold_in(root, chain_id)` — stable under any pool
        size, which is what makes placement bitwise-irrelevant).
        Returns (GibbsState, SLDAModel, ElasticReport).  With
        `resume=True`, continues from the latest durable checkpoint in
        `ckpt_dir` (fresh start if there is none)."""
        sup, el = self.sup, self.elastic
        plan = sup.plan
        m = plan.n_chains
        R = self.cfg.n_iters // el.round_iters
        round_plan = sup.make_round_plan(el.round_iters)
        bpr = round_plan.n_boundaries()

        chain_keys = jax.vmap(
            lambda c: jax.random.fold_in(root_key, c))(jnp.arange(m))
        ks = jax.vmap(jax.random.split)(chain_keys)
        state, z_fill = sup._init(plan, ks[:, 0])
        self._base = base = ks[:, 1]

        bk = {"alive": np.ones(m, bool), "epoch": np.zeros(m, np.int32),
              "restarts": np.zeros(m, np.int32),
              "grace": np.zeros(m, np.int32),
              "latched": np.zeros(m, np.uint32),
              "progress": np.zeros(m, np.int32)}
        wall = 0
        resumed_from = None
        if resume:
            if self.manager is None:
                raise ValueError("resume=True needs a ckpt_dir")
            durable = self.manager.latest_durable()
            if durable is not None:
                extra = read_manifest(self.ckpt_dir,
                                      durable).get("extra", {})
                fresh = state
                state, _info = restore_elastic(
                    self.ckpt_dir, durable, state,
                    lambda i: jax.tree.map(lambda x: x[i], fresh))
                for name in ("progress", "alive", "epoch", "restarts"):
                    if name in extra:
                        bk[name][:] = np.asarray(extra[name])
                wall = int(extra.get("wall_round", durable))
                resumed_from = durable
        history, placements = [], []
        straggles, late = [], {}
        self._repack(bk, placements, "resume" if resumed_from is not None
                     else "init")
        pending = list(self.events)
        max_wall = R * (2 + m * max(1, sup.recovery.max_restarts))

        while True:
            active = bk["alive"] & (bk["progress"] < R)
            if not active.any():
                break
            if not el.catch_up and wall >= R:
                break
            if wall >= max_wall:
                raise RuntimeError(
                    f"elastic loop exceeded {max_wall} wall rounds — "
                    "restart thrash; see the event history")
            events = []
            for ev in [e for e in pending if e.at_round <= wall]:
                pending.remove(ev)
                state = self._apply_event(ev, state, bk, events,
                                          placements, straggles)
            if self.preemption.triggered:
                self._drain(state, bk, wall, events)
                history.append({"wall_round": wall, "events": events})
                break
            active = bk["alive"] & (bk["progress"] < R)
            if not active.any():
                history.append({"wall_round": wall, "events": events})
                break

            keys = sup._fold_keys(base, bk["epoch"], bk["progress"])
            it0 = int(bk["progress"].min()) * bpr
            new_state, status_np = sup.run_round(
                round_plan, keys, state, bk["alive"], it0)
            state = self._merge(new_state, state,
                                jnp.asarray(active, bool))
            status_np = np.where(active, status_np, 0).astype(np.uint32)
            state = sup._apply_recovery(
                state, status_np, alive=bk["alive"], epoch=bk["epoch"],
                restarts=bk["restarts"], grace=bk["grace"], base=base,
                events=events)
            reset = set()
            for e in events:
                # a health-probe restart resets that chain's logical
                # clock: a restore replays from the checkpoint's round,
                # a fresh init starts over (its stream is new anyway)
                if e.get("action", "").startswith("restart_from_step_"):
                    step = int(e["action"].rsplit("_", 1)[1])
                    xtra = read_manifest(self.ckpt_dir,
                                         step).get("extra", {})
                    prog = xtra.get("progress")
                    bk["progress"][e["chain"]] = (
                        int(prog[e["chain"]]) if prog is not None else 0)
                    reset.add(e["chain"])
                elif e.get("action") == "restart_fresh_init":
                    bk["progress"][e["chain"]] = 0
                    reset.add(e["chain"])
            bk["grace"] = np.maximum(bk["grace"] - 1, 0)
            bk["latched"] |= status_np
            sup._check_min_alive(bk["alive"], bk["latched"])
            # restarted chains rewound their clock this round — the work
            # they did is gone, so they take no progress credit
            advance = active & bk["alive"]
            for c in reset:
                advance[c] = False
            bk["progress"] = bk["progress"] + advance.astype(np.int32)
            finish = self._round_clock(bk, events, placements, straggles,
                                       late)
            wall += 1
            if self.manager is not None:
                self.manager.maybe_save(wall, state,
                                        extra=self._extra(bk, wall))
            history.append({"wall_round": wall,
                            "progress": [int(x) for x in bk["progress"]],
                            "status": [int(s) for s in status_np],
                            "finish_s": {str(d): t
                                         for d, t in finish.items()},
                            "events": events})

        if self.manager is not None and not self.preemption.triggered:
            self.manager.flush()
        models = plan._export(state)
        state = GibbsState(z=plan.corpus.merge_padded(state.z, z_fill),
                           ndt=state.ndt, ntw=state.ntw, nt=state.nt,
                           eta=state.eta)
        report = ElasticReport(
            alive=bk["alive"], status=bk["latched"],
            restarts=bk["restarts"], progress=bk["progress"],
            wall_rounds=wall, logical_rounds=R, history=history,
            pool_history=list(self.pool.history), placements=placements,
            preempted=self.preemption.triggered,
            resume_round=resumed_from, sim_seconds=self.clock.now(),
            round_traces=sup.round_traces)
        return state, models, report


# --------------------------------------------------- end-to-end entry point


def elastic_run_average(key, train, test, cfg: SLDAConfig, m: int, *,
                        devices=2, rule: str = "weighted",
                        elastic: ElasticConfig | None = None, health=None,
                        recovery=None, ckpt_dir=None, events=(),
                        clock=None, preemption=None, resume: bool = False):
    """The elastic form of `supervised_run_average`: train M chains
    under the elastic runtime, predict with every chain, combine with
    the final alive mask.  Returns (ŷ [D_test], ElasticReport)."""
    from repro.core import combine
    from repro.core.parallel import _combine_weighted, _predict_chains_jit
    from repro.core.types import _concat_corpora
    k1, k2 = jax.random.split(key)
    shards = build_schedule(partition(train, m), cfg)
    runner = ElasticRunner(shards, cfg, devices=devices, elastic=elastic,
                           health=health, recovery=recovery,
                           ckpt_dir=ckpt_dir, events=events, clock=clock,
                           preemption=preemption)
    _, models, report = runner.train(k1, resume=resume)
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
