"""Mesh traffic: whole Weighted Average fits of the four-chip runner,
back to back.

Parameters (traffic file): `entry`, which must be "weighted".  Set-up
makes the corpus from the seed as drivers/offline.py does, places the
training and test sets replicated on a `Mesh` of the cell's first
`chips` devices (one axis, "chains"), and compiles, or loads from the
cache, the runner's slice program by one whole fit.  The window runs
`launch.slda_parallel.parallel_slda_shard_map(rule="weighted",
return_chains=True)` with a fresh key each, `n_chains / chips` chains
to a chip, until `seconds` have passed.  The check reads each fit's
per-chain models and ŷ from the `ShardFit` the runner hands out, and
compares them as drivers/offline.py does for Weighted Average.
"""
from __future__ import annotations

import time

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import work
from bench.drivers import offline
from bench.gen import world_and_docs
from bench.hyper import slda_config

AXIS = "chains"


class Driver(offline.Driver):
    kind = "offline"

    def __init__(self, conf: dict, traffic: dict, seed: int, chips: int):
        if traffic["entry"] != "weighted":
            raise ValueError(f"unknown entry {traffic['entry']!r}")
        super().__init__(conf, traffic, seed, chips)

    def setup(self, seconds: float):
        from repro.core import Corpus
        from repro.launch.slda_parallel import (mesh_supports_pallas,
                                                parallel_slda_shard_map)
        conf = self.conf
        t0 = time.perf_counter()
        n_tr = conf["n_train"]
        _, self.train_docs = world_and_docs(self.seed, conf, n_tr, 0)
        _, self.test_docs = world_and_docs(self.seed, conf,
                                           conf["n_docs"] - n_tr, 1)
        mesh = Mesh(np.array(jax.devices()[:self.chips]), (AXIS,))
        # every chip holds the whole training and test sets: its chains
        # train on their own shards and predict test ‖ train
        everywhere = NamedSharding(mesh, P())
        train = jax.device_put(Corpus(*self.train_docs), everywhere)
        test = jax.device_put(Corpus(*self.test_docs), everywhere)
        jax.block_until_ready((train, test))
        self.setup_parts = {"corpus_s": time.perf_counter() - t0}
        self.cfg = slda_config(conf)
        self.chains_per_chip = conf["n_chains"] // self.chips
        self.route = "pallas" if mesh_supports_pallas(mesh) else "jnp"

        def call(k):
            fit = parallel_slda_shard_map(
                k, train, test, self.cfg, mesh, axis=AXIS, rule="weighted",
                chains_per_device=self.chains_per_chip, return_chains=True)
            self._capture = {"models": fit.models,
                             "yhat": [fit.yhat_chains]}
            return fit.yhat
        self.call = call
        self._restore = lambda: None
        # one whole fit: compiles (or loads) the slice program and the
        # host glue (partition, concatenation), all of which the window runs
        jax.block_until_ready(call(jax.random.fold_in(self.key, 2 ** 30)))
        jax.block_until_ready(self._capture)
        self.setup_parts["programs_s"] = time.perf_counter() - t0 \
            - self.setup_parts["corpus_s"]
        self._capture = {}
        self.draws_per_fit = work.fit_draws(
            "weighted", conf, self.lengths(self.train_docs),
            self.lengths(self.test_docs))

    def context(self) -> dict:
        return dict(super().context(), notes={
            "chains_per_chip": self.chains_per_chip, "route": self.route})
