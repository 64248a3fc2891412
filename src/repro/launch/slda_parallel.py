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

The training phase contains ZERO collectives — `shard_map` makes that
structural, not accidental: the per-slice function has no `psum`/`all_*`
in it (the chain batch is slice-local), so the lowered HLO cannot
contain a collective.  The only communication in the whole algorithm is
the final `all_gather` of the per-chain test predictions (a [D_test]
float vector each — KBs), which implements the paper's combination
stage (Eq. 6).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import (Corpus, SLDAConfig, build_schedule, combine,
                        devices_support_pallas, partition)
from repro.core.parallel import predict_chains_keyed, train_chains_keyed


def mesh_supports_pallas(mesh: Mesh) -> bool:
    """True when every device in the mesh compiles the sLDA Pallas kernels
    natively (TPU).  On CPU/GPU meshes the kernels would run in interpret
    mode — correct but slower than the batched-jnp twins, so the runner
    keeps use_pallas off there.  (Thin alias of the shared
    `core.devices_support_pallas` predicate — the one platform check,
    also behind `SLDAConfig.resolve_backend`.)"""
    return devices_support_pallas(mesh.devices.flat)


def parallel_slda_shard_map(key, train: Corpus, test: Corpus,
                            cfg: SLDAConfig, mesh: Mesh,
                            axis: str = "data", rule: str = "simple",
                            auto_pallas: bool = True,
                            chains_per_device: int | None = None,
                            alive=None, auto_quarantine: bool = True,
                            return_report: bool = False):
    """Run M = mesh.shape[axis] × chains_per_device chains, a chain batch
    per mesh slice, then combine predictions.  Returns ŷ [D_test].

    chains_per_device=None reads `cfg.chains_per_device` (default 1 —
    the one-chain-per-device special case).  auto_pallas=True flips
    `cfg.use_pallas` on when the mesh backend compiles the kernels
    natively (TPU), so chains take the fused chain-batched kernel paths
    without the caller having to re-tune the config per backend; an
    explicit `use_pallas=True` in cfg is always honored (including
    interpret mode on CPU meshes, which the communication-freedom test
    exercises).

    cfg.length_buckets > 0 routes the chain phases through the ragged
    execution layer (DESIGN.md §Ragged-execution): shards and test are
    length-bucketed HERE — outside shard_map, where lengths are concrete
    — and the bucketed pytrees flow through the same per-slice chain
    functions (every bucket's arrays carry the chain dim, so the specs
    below still shard only that axis; zero collectives is untouched).

    Fault tolerance (DESIGN.md §Fault-model): `alive` [M] masks chains
    out of the combine — communication-freedom makes the drop EXACT.
    With `alive=None` and `auto_quarantine=True` (default), any chain
    whose gathered predictions or train stats came back non-finite is
    quarantined automatically — a NaN-poisoned replica cannot
    contaminate ŷ.  When every chain is healthy the mask is all-ones,
    which evaluates to the identical combine expressions, so healthy
    runs are unchanged.  `return_report=True` additionally returns
    {"alive": ..., "n_quarantined": ...}."""
    if auto_pallas and not cfg.use_pallas and mesh_supports_pallas(mesh):
        cfg = dataclasses.replace(cfg, use_pallas=True)
    cpd = cfg.chains_per_device if chains_per_device is None \
        else chains_per_device
    mesh_m = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    m = mesh_m * cpd
    shards = partition(train, m)                      # [M, D/M, ...]
    shard_spec, test_spec = P(axis), P()
    if cfg.length_buckets > 0:
        # schedules are built HERE — outside shard_map, where lengths
        # are concrete; inside each slice `train_chains_keyed` builds
        # its plan from the sharded schedule (plan per shard)
        shards = build_schedule(shards, cfg)
        test = build_schedule(test, cfg)
        shard_spec = jax.tree.map(lambda _: P(axis), shards)
        test_spec = jax.tree.map(lambda _: P(), test)

    def chain_fn(key_rep, shard_blk, test_blk):
        # cpd chains per mesh slice: the in_spec hands this slice cpd
        # consecutive shards.  Chain keys are folded from the replicated
        # base key INSIDE the shard, one per GLOBAL chain id — a
        # pre-split [M, 2] keys array sharded over `axis` makes GSPMD
        # lower the threefry split as a cross-device combine (an
        # all-reduce), which would break the zero-collective guarantee.
        base = jax.lax.axis_index(axis) * cpd
        keys = jax.vmap(lambda i: jax.random.fold_in(key_rep, base + i))(
            jnp.arange(cpd))
        ks = jax.vmap(jax.random.split)(keys)         # [cpd, 2, key]
        _, models = train_chains_keyed(ks[:, 0], shard_blk, cfg)  # NO collectives
        yhat = predict_chains_keyed(ks[:, 1], models, test_blk, cfg)
        stats = jnp.stack([models.train_mse, models.train_acc], axis=-1)
        # the ONLY communication in the algorithm:
        yhat_all = jax.lax.all_gather(yhat, axis)     # [mesh_m, cpd, D_test]
        stats_all = jax.lax.all_gather(stats, axis)   # [mesh_m, cpd, 2]
        return (yhat_all.reshape(m, yhat.shape[-1]),
                stats_all.reshape(m, 2))

    fn = jax.shard_map(
        chain_fn, mesh=mesh,
        in_specs=(P(), shard_spec, test_spec),
        out_specs=(P(), P()),
        check_vma=False,   # chain-local scans carry unvarying state
    )
    yhat_all, stats_all = fn(key, shards, test)
    if alive is None and auto_quarantine:
        # the gathered per-chain vectors are the chain's only output —
        # a non-finite row means the chain is unusable, full stop
        alive = (jnp.isfinite(yhat_all).all(axis=-1)
                 & jnp.isfinite(stats_all).all(axis=-1)).astype(jnp.float32)
    if rule == "simple":
        yhat = combine.simple_average(yhat_all, alive=alive)
    elif rule == "weighted":
        if cfg.label_type == "binary":
            yhat = combine.weighted_average(yhat_all,
                                            train_acc=stats_all[:, 1],
                                            alive=alive)
        else:
            yhat = combine.weighted_average(yhat_all,
                                            train_mse=stats_all[:, 0],
                                            alive=alive)
    elif rule == "median":
        yhat = combine.median(yhat_all, alive=alive)
    else:
        raise ValueError(rule)
    if return_report:
        a = None if alive is None else jnp.asarray(alive)
        report = {"alive": a,
                  "n_quarantined": (0 if a is None
                                    else int(m - float(a.sum())))}
        return yhat, report
    return yhat
