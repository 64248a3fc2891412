"""Offline traffic: whole fits of one entry point, back to back.

Parameters (traffic file): `entry`, one of ENTRIES.  Set-up makes the
corpus on the device from the seed and compiles, or loads from the
cache, every program the window runs: "weighted" by one whole fit of
the host orchestrator, "train_chains" ahead of time.  The window then
runs fits with a fresh key each until `seconds` have passed, and waits
for the last.  The check reads what every fit of the window produced.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import checks, reference, work
from bench.gen import seed_key, world_and_docs
from bench.hyper import slda_config

ENTRIES = ("weighted", "train_chains")


class Driver:
    kind = "offline"

    def __init__(self, conf: dict, traffic: dict, seed: int, chips: int):
        if traffic["entry"] not in ENTRIES:
            raise ValueError(f"unknown entry {traffic['entry']!r}")
        self.conf, self.traffic, self.seed, self.chips = \
            conf, traffic, seed, chips
        self.entry = traffic["entry"]
        self.key = seed_key(seed)
        self.fits = []          # (outputs, captured) of each window fit
        self._capture = None

    # ------------------------------------------------------------ set-up

    def setup(self, seconds: float):
        from repro.core import Corpus, parallel
        conf = self.conf
        t0 = time.perf_counter()
        # training and test documents each with their own length profile,
        # so every seed trains on and predicts the same number of tokens
        n_tr = conf["n_train"]
        _, self.train_docs = world_and_docs(self.seed, conf, n_tr, 0)
        _, self.test_docs = world_and_docs(self.seed, conf,
                                           conf["n_docs"] - n_tr, 1)
        jax.block_until_ready((self.train_docs, self.test_docs))
        self.setup_parts = {"corpus_s": time.perf_counter() - t0}
        self.cfg = slda_config(conf)
        m = conf["n_chains"]
        train = Corpus(*self.train_docs)
        test = Corpus(*self.test_docs)
        if self.entry == "weighted":
            self._install_capture(parallel)
            algo = parallel.ALGORITHMS["weighted"]
            self.call = lambda k: algo(k, train, test, self.cfg, m)
            # one whole fit: compiles (or loads) both programs and the
            # orchestrator's glue, all of which the window runs
            jax.block_until_ready(self.call(jax.random.fold_in(self.key,
                                                               2 ** 30)))
        else:
            shards = parallel.partition(train, m)
            fit = jax.jit(parallel.train_chains, static_argnums=(2,)).lower(
                self.key, shards, self.cfg).compile()
            self.call = lambda k: fit(k, shards)
        self.setup_parts["programs_s"] = time.perf_counter() - t0 \
            - self.setup_parts["corpus_s"]
        self._reset_capture()
        self.draws_per_fit = work.fit_draws(
            self.entry, conf, self.lengths(self.train_docs),
            self.lengths(self.test_docs))

    def _install_capture(self, parallel):
        """Keep what Weighted Average's chain phases return: the models of
        its `train_chains` program and the per-chain ŷ of its
        `predict_chains` program, as the same compiled programs run."""
        train_jit, predict_jit = (parallel._train_chains_jit,
                                  parallel._predict_chains_jit)
        driver = self

        def train_chains(*a, **kw):
            out = train_jit(*a, **kw)
            if driver._capture is not None:
                driver._capture["models"] = out
            return out

        def predict_chains(*a, **kw):
            out = predict_jit(*a, **kw)
            if driver._capture is not None:
                driver._capture.setdefault("yhat", []).append(out)
            return out

        parallel._train_chains_jit = train_chains
        parallel._predict_chains_jit = predict_chains
        self._restore = lambda: (
            setattr(parallel, "_train_chains_jit", train_jit),
            setattr(parallel, "_predict_chains_jit", predict_jit))
        self._capture = {}

    def _reset_capture(self):
        if self._capture is not None:
            self._capture = {}

    @staticmethod
    def lengths(docs):
        return np.asarray(docs.mask.sum(-1)).astype(np.int64)

    # ------------------------------------------------------------ window

    def window(self, seconds: float) -> dict:
        base = jax.random.fold_in(self.key, 17)
        t0 = time.perf_counter()
        i = 0
        while True:
            with jax.profiler.TraceAnnotation("bench.fit"):
                out = jax.block_until_ready(self.call(
                    jax.random.fold_in(base, i)))
            self.fits.append((out, self._capture))
            self._reset_capture()
            i += 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        if self._capture is not None:
            self._restore()
        draws = {k: v * i for k, v in self.draws_per_fit.items()}
        return {"window_s": elapsed, "fits": i, "draws": draws,
                "attempted": i}

    def failed(self) -> int:
        """Fits of the window whose output is not finite (read after the
        window closed)."""
        return sum(0 if all(bool(jnp.isfinite(x).all())
                            for x in jax.tree.leaves(out)) else 1
                   for out, _ in self.fits)

    # ------------------------------------------------------------- check

    def check(self) -> dict:
        """The numbers of checks.py, the worst over the window's fits."""
        conf = self.conf
        hp = reference.HP.of(conf)
        binary = conf["label_type"] == "binary"
        m = conf["n_chains"]
        k_ref = jax.random.fold_in(self.key, 99)
        y_te = np.asarray(self.test_docs.y, np.float64)
        y_tr = np.asarray(self.train_docs.y, np.float64)
        with jax.default_matmul_precision("highest"):
            if self.entry == "weighted":
                return self._check_weighted(hp, binary, m, k_ref, y_te, y_tr)
            return self._check_train(hp, m, k_ref, y_te)

    def _word_counts(self):
        return checks.word_counts(self.train_docs.tokens,
                                  self.train_docs.mask,
                                  self.conf["n_chains"],
                                  self.conf["vocab_size"])

    def _check_weighted(self, hp, binary, m, k_ref, y_te, y_tr):
        ref = reference.weighted_average(k_ref, self.train_docs,
                                         self.test_docs, hp, m, binary)
        wc = self._word_counts()
        n_te = y_te.shape[0]
        nums = {"count_gap": 0.0, "combine_gap": 0.0, "mse_excess": -np.inf}
        for out, cap in self.fits:
            yhat = np.concatenate([np.asarray(y, np.float64)
                                   for y in cap["yhat"]], axis=1)
            w = reference.weights_from_predictions(yhat[:, n_te:], y_tr,
                                                   binary)
            nums["count_gap"] = max(nums["count_gap"], checks.count_gap(
                cap["models"].phi, wc, self.conf["beta"]))
            nums["combine_gap"] = max(nums["combine_gap"], checks.combine_gap(
                out, yhat[:, :n_te], w, y_te.std()))
            nums["mse_excess"] = max(nums["mse_excess"], checks.mse_excess(
                out, y_te, ref["out"]))
        return nums

    def _check_train(self, hp, m, k_ref, y_te):
        """count_gap on every fit; the held-out quality of the chains of
        up to three fits drawn from the seed, each read by the
        reference's own predictor beside the reference's own chains."""
        shards = reference.shards_of(self.train_docs, m)
        ref_models = reference.train(jax.random.split(k_ref, m), shards, hp)
        k_pred = jax.random.split(jax.random.fold_in(k_ref, 1), m)
        _, ref_yhat = reference.predict(k_pred, ref_models, self.test_docs,
                                        hp)
        ref_out = np.asarray(ref_yhat, np.float64).mean(0)
        wc = self._word_counts()
        nums = {"count_gap": 0.0, "mse_excess": -np.inf}
        for out, _ in self.fits:
            nums["count_gap"] = max(nums["count_gap"], checks.count_gap(
                out.phi, wc, self.conf["beta"]))
        rng = np.random.default_rng(self.seed)
        pick = rng.choice(len(self.fits), min(3, len(self.fits)),
                          replace=False)
        for i in pick:
            out = self.fits[int(i)][0]
            models = reference.Models(out.phi, out.eta, out.train_mse,
                                      out.train_acc)
            _, yhat = reference.predict(k_pred, models, self.test_docs, hp)
            nums["mse_excess"] = max(nums["mse_excess"], checks.mse_excess(
                np.asarray(yhat, np.float64).mean(0), y_te, ref_out))
        return nums

    # ----------------------------------------------------------- context

    def context(self) -> dict:
        return {"entry": self.entry, "draws_per_fit": self.draws_per_fit,
                "setup_parts": self.setup_parts}


