"""Multi-device sLDA chain runner: the paper's algorithm under shard_map.

Each mesh slice owns `chains_per_device` chains and their training
shards, so the paper's M is decoupled from the device count:
M = mesh.shape[axis] × chains_per_device.  The local chain batch runs
through the CHAIN-BATCHED core entry points
(`core.parallel.train_chains_keyed` / `predict_chains_keyed`), which on
TPU lower to the grid-(chains, doc_blocks) fused Pallas launches of
DESIGN.md §Chain-batched — one launch per EM boundary for all local
chains, the shared test-token tiles read once per doc block rather than
once per chain.

The training and prediction phases contain ZERO collectives —
`shard_map` makes that structural, not accidental: the per-slice
function has no `psum`/`all_*` in it before the combine (the chain batch
is slice-local), so the lowered HLO cannot contain a collective there.
The only communication in the whole algorithm is the ONE `all_gather`
of the per-chain predictions (a float vector per chain — KBs), which
implements the paper's combination stage (Eqs. 6-9).  With
rule="weighted" every chain predicts the test set AND the full training
set (Section III-C(d)), as `core.parallel.run_weighted_average` does,
and the weights come from the same `_combine_weighted`.

The slice program is jitted as `shard_chains` (XLA module
`jit_shard_chains`); inside it the gather carries the named scope
`chain_gather` and the weighting and averaging after it `combine`.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import (Corpus, SLDAConfig, SLDAModel, build_schedule,
                        combine, devices_support_pallas, parallel)
from repro.core.types import _concat_corpora


class ShardFit(NamedTuple):
    """What one call of the runner computed (`return_chains=True`).

    Chain i is the chain that trained on `partition(train, M)[i]`; the
    per-chain arrays stay sharded over the mesh's chain axis."""
    yhat: jax.Array           # [D_test] the combined prediction
    models: SLDAModel         # [M, ...] each chain's exports
    yhat_chains: jax.Array    # [M, D_test] — with rule="weighted"
                              # [M, D_test + D_train], test ‖ train
    alive: jax.Array | None   # [M] the mask the combine used


def mesh_supports_pallas(mesh: Mesh) -> bool:
    """True when every device in the mesh compiles the sLDA Pallas kernels
    natively (TPU).  On CPU/GPU meshes the kernels would run in interpret
    mode — correct but slower than the batched-jnp twins, so the runner
    keeps use_pallas off there.  (Thin alias of the shared
    `core.devices_support_pallas` predicate — the one platform check,
    also behind `SLDAConfig.resolve_backend`.)"""
    return devices_support_pallas(mesh.devices.flat)


def shard_chains(key, shards, corpus, train_y, alive, *, cfg: SLDAConfig,
                 mesh: Mesh, axis: str, cpd: int, rule: str, n_test: int,
                 quarantine: bool):
    """The slice program: train the slice's `cpd` chains on their shards,
    predict `corpus` (test, or test ‖ train for rule="weighted"), gather
    the per-chain predictions once and combine them.  Returns
    (ŷ [D_test], models [M, ...], per-chain ŷ [M, D], alive [M] | None);
    the per-chain outputs stay sharded over `axis`."""
    def chain_fn(key_rep, shard_blk, corpus_blk, train_y, alive):
        # Chain keys are folded from the replicated base key INSIDE the
        # shard, one per GLOBAL chain id, so any layout of the same M
        # chains draws the same numbers — a pre-split [M, 2] keys array
        # sharded over `axis` makes GSPMD lower the threefry split as a
        # cross-device combine (an all-reduce), which would break the
        # zero-collective guarantee.
        base = jax.lax.axis_index(axis) * cpd
        keys = jax.vmap(lambda i: jax.random.fold_in(key_rep, base + i))(
            jnp.arange(cpd))
        ks = jax.vmap(jax.random.split)(keys)         # [cpd, 2, key]
        # looked up at call time, as run_weighted_average's programs are
        _, models = parallel.train_chains_keyed(ks[:, 0], shard_blk, cfg)
        yhat = parallel.predict_chains_keyed(ks[:, 1], models, corpus_blk,
                                             cfg)     # [cpd, D]
        stats = jnp.stack([models.train_mse, models.train_acc], axis=-1)
        with jax.named_scope("chain_gather"):         # the ONLY collective
            gathered = jax.lax.all_gather(
                jnp.concatenate([yhat, stats], axis=-1), axis, tiled=True)
        with jax.named_scope("combine"):
            yhat_all, stats_all = gathered[:, :-2], gathered[:, -2:]
            if alive is None and quarantine:
                # a non-finite prediction or train statistic means the
                # chain is unusable, full stop
                alive = (jnp.isfinite(yhat_all).all(axis=-1)
                         & jnp.isfinite(stats_all).all(axis=-1)
                         ).astype(jnp.float32)
            if rule == "weighted":
                out = parallel._combine_weighted(
                    yhat_all[:, :n_test], yhat_all[:, n_test:], train_y,
                    cfg, alive)
            else:
                out = combine.COMBINERS[rule](yhat_all, alive=alive)
        return out, models, yhat, alive

    fn = jax.shard_map(
        chain_fn, mesh=mesh,
        in_specs=(P(), P(axis), P(), P(), P()),
        out_specs=(P(), P(axis), P(axis), P()),
        check_vma=False,   # chain-local scans carry unvarying state
    )
    return fn(key, shards, corpus, train_y, alive)


_shard_chains_jit = jax.jit(shard_chains, static_argnames=(
    "cfg", "mesh", "axis", "cpd", "rule", "n_test", "quarantine"))


def parallel_slda_shard_map(key, train: Corpus, test: Corpus,
                            cfg: SLDAConfig, mesh: Mesh,
                            axis: str = "data", rule: str = "simple",
                            auto_pallas: bool = True,
                            chains_per_device: int | None = None,
                            alive=None, auto_quarantine: bool = True,
                            return_chains: bool = False):
    """Run M = mesh.shape[axis] × chains_per_device chains, a chain batch
    per mesh slice, then combine predictions.  Returns ŷ [D_test].

    rule: "simple" (Eq. 7), "weighted" (Eqs. 8-9: every chain also
    predicts the full training set, in one fused pass over test ‖ train,
    and `core.parallel._combine_weighted` weights the chains by that
    pass's MSE or accuracy) or "median".

    chains_per_device=None reads `cfg.chains_per_device` (default 1 —
    the one-chain-per-device special case).  auto_pallas=True flips
    `cfg.use_pallas` on when the mesh backend compiles the kernels
    natively (TPU), so chains take the fused chain-batched kernel paths
    without the caller having to re-tune the config per backend; an
    explicit `use_pallas=True` in cfg is always honored (including
    interpret mode on CPU meshes, which the communication-freedom test
    exercises).

    The partition, the concatenation and any length schedule are built
    HERE, on the host and outside the jitted slice program, where
    lengths are concrete (host span `slda.shard.schedule`; the whole
    call is `slda.shard.fit`).  cfg.length_buckets > 0 routes the chain
    phases through the ragged execution layer (DESIGN.md
    §Ragged-execution): every bucket's arrays carry the chain dim, so
    the specs still shard only that axis.  Inputs placed replicated on
    the mesh (`NamedSharding(mesh, P())`) reach each slice with no
    communication.

    Fault tolerance (DESIGN.md §Fault-model): `alive` [M] masks chains
    out of the combine — communication-freedom makes the drop EXACT.
    With `alive=None` and `auto_quarantine=True` (default), any chain
    whose predictions or train stats came back non-finite is
    quarantined automatically — a NaN-poisoned replica cannot
    contaminate ŷ.  When every chain is healthy the mask is all-ones,
    which evaluates to the identical combine expressions, so healthy
    runs are unchanged.

    `return_chains=True` returns a `ShardFit` in place of ŷ: the
    combined ŷ, the per-chain models and per-chain ŷ in global chain
    order, and the alive mask (the quarantined chains are its zeros)."""
    if rule not in combine.COMBINERS:
        raise ValueError(rule)
    if auto_pallas and not cfg.use_pallas and mesh_supports_pallas(mesh):
        cfg = dataclasses.replace(cfg, use_pallas=True)
    cpd = cfg.chains_per_device if chains_per_device is None \
        else chains_per_device
    mesh_m = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    m = mesh_m * cpd
    with TraceAnnotation("slda.shard.fit"):
        with TraceAnnotation("slda.shard.schedule"):
            shards = parallel.partition(train, m)     # [M, D/M, ...]
            corpus = (_concat_corpora(test, train) if rule == "weighted"
                      else test)
            if cfg.length_buckets > 0:
                shards = build_schedule(shards, cfg)
                corpus = build_schedule(corpus, cfg)
        yhat, models, yhat_chains, alive = _shard_chains_jit(
            key, shards, corpus, train.y, alive, cfg=cfg, mesh=mesh,
            axis=axis, cpd=cpd, rule=rule, n_test=test.n_docs,
            quarantine=auto_quarantine)
    return (ShardFit(yhat, models, yhat_chains, alive) if return_chains
            else yhat)
