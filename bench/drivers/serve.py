"""Open-loop serving traffic: fresh documents arrive on a schedule fixed
by the seed, whatever the service does.

Parameters (traffic file): `rate_per_s`, the offered load, and
`p95_limit_ms`, the latency limit the rate was chosen under (the run
prints its p95 beside it on the notes line).  The
window holds exactly round(rate × seconds) arrivals, due at sorted
uniform times (a Poisson process given its count), so every seed
offers the same load.  Documents are new draws of the configuration's
world with the same multiset of lengths for every seed, none repeated,
so the result cache never answers and every request reaches the device.

The dispatcher is work-conserving: it submits every request that is
due (the service dispatches a full micro-batch by itself), and when no
request is due and some are pending it flushes a partial micro-batch;
with nothing pending it sleeps until the next arrival.  A request's
latency runs from its due time to its answer.  The host's own pauses in
the window are kept for the notes line: every garbage collection, by
generation, and the longest flush, submit burst and wait.
"""
from __future__ import annotations

import gc
import time

import jax
import numpy as np

from bench import checks, reference
from bench.gen import Docs, length_profile, make_docs, seed_key, \
    world_and_docs
from bench.hyper import slda_config

SAMPLE = 1024       # answers read by the reference in the check


class Driver:
    kind = "serve"

    def __init__(self, conf: dict, traffic: dict, seed: int, chips: int):
        self.conf, self.traffic, self.seed, self.chips = \
            conf, traffic, seed, chips
        self.key = seed_key(seed)
        self.setup_parts = {}

    def setup(self, seconds: float):
        """Train the chains, start and warm the service, and make the
        window's requests: nothing is made inside the window."""
        from repro.core import Corpus, parallel
        from repro.serving import ServiceConfig, SLDAPredictionService
        conf = self.conf
        self.cfg = slda_config(conf)
        m = conf["n_chains"]
        (phi, eta), docs = world_and_docs(self.seed, conf, conf["n_train"])
        self.train_docs = docs
        t0 = time.perf_counter()
        fit = jax.jit(parallel.train_chains, static_argnums=(2,))
        self.models = jax.block_until_ready(fit(
            jax.random.fold_in(self.key, 3),
            parallel.partition(Corpus(*docs), m), self.cfg))
        lengths = np.asarray(docs.mask.sum(-1)).astype(int)
        svc_cfg = ServiceConfig.calibrated(lengths,
                                           max_doc_len=conf["max_len"])
        self.batch = svc_cfg.batch_docs
        self.svc = SLDAPredictionService(self.models, self.cfg, svc_cfg,
                                         key=jax.random.fold_in(self.key, 4))
        self._phi_eta = (phi, eta)
        self.setup_parts["train_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        # warm-up: a full micro-batch and a partial one, of documents the
        # window never sends
        warm = self._docs(self.batch + self.batch // 2, stream=2)
        for toks in warm[0]:
            self.svc.submit(toks)
        self.svc.drain()
        self.stats0 = self.svc.stats()
        self.setup_parts["warm_service_s"] = time.perf_counter() - t0
        self.due = due_times(self.seed, self.traffic["rate_per_s"], seconds)
        self.requests, self.request_docs = self._docs(len(self.due),
                                                      stream=1)

    def _docs(self, n: int, stream: int):
        """n fresh documents as host token lists, with their labels."""
        phi, eta = self._phi_eta
        conf = self.conf
        d = make_docs(jax.random.fold_in(self.key, 10 + stream), phi, eta,
                      jax.numpy.asarray(length_profile(
                          n, conf["length"], conf["max_len"])),
                      max_len=conf["max_len"], alpha=conf["alpha"],
                      rho=conf["rho"],
                      binary=conf["label_type"] == "binary")
        toks = np.asarray(d.tokens)
        lens = np.asarray(d.mask.sum(-1)).astype(int)
        return [toks[i, :lens[i]] for i in range(n)], d

    # ------------------------------------------------------------ window

    def window(self, seconds: float) -> dict:
        svc, due, reqs = self.svc, self.due, self.requests
        n = len(due)
        rids = np.zeros(n, np.int64)
        t_sub = np.zeros(n)
        host = HostPauses()
        gc.callbacks.append(host.on_gc)
        t0 = time.perf_counter()
        i = 0
        try:
            while i < n:
                now = time.perf_counter() - t0
                if due[i] <= now:
                    with jax.profiler.TraceAnnotation("bench.submit"):
                        while i < n and due[i] <= time.perf_counter() - t0:
                            t_sub[i] = time.perf_counter()
                            rids[i] = svc.submit(reqs[i])
                            i += 1
                    host.took("submit", now + t0)
                    continue
                t1 = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.flush"):
                    done = svc.flush()
                host.took("flush", t1)
                if not done:
                    t1 = time.perf_counter()
                    with jax.profiler.TraceAnnotation("bench.wait"):
                        time.sleep(max(0.0, min(due[i] - now, 0.002)))
                    host.took("wait", t1)
            t_close = t0 + seconds
            with jax.profiler.TraceAnnotation("bench.drain"):
                svc.drain()
            t_end = time.perf_counter()
        finally:
            gc.callbacks.remove(host.on_gc)
        self.host_pauses = host.summary()
        self.t0, self.rids, self.t_sub = t0, rids, t_sub
        res = [svc.result(int(r)) for r in rids]
        self.results = res
        ok = np.array([r.status == "ok" for r in res])
        self.latency_s, self.lag_s, t_done = timings(
            t0, due, t_sub, np.array([r.latency_s for r in res]), ok, t_end)
        st = svc.stats()
        return {"window_s": seconds, "attempted": n,
                "failed": int(n - ok.sum()),
                "answered_in_window": int((ok & (t_done <= t_close)).sum()),
                "dispatches": st["dispatches"] - self.stats0["dispatches"],
                "dummy_slots": st["dummy_slots"] - self.stats0["dummy_slots"],
                "batch_docs": self.batch,
                "result_cache_hits": st["result_cache_hits"]
                - self.stats0["result_cache_hits"],
                "latency_ms": (self.latency_s * 1e3).tolist(),
                "lag_ms": (self.lag_s * 1e3).tolist()}

    # ------------------------------------------------------------- check

    def check(self) -> dict:
        """Structure of every answer; the quality of a sample of them
        against the reference's own chains on the same documents."""
        conf = self.conf
        ok = [i for i, r in enumerate(self.results) if r.status == "ok"]
        if not ok:
            return {"zbar_gap": None, "answer_gap": None, "mse_excess": None}
        lens = np.array([len(self.requests[i]) for i in ok])
        zbar = np.stack([self.results[i].zbar for i in ok])
        chains = np.stack([self.results[i].yhat_chains for i in ok])
        comb = np.array([self.results[i].yhat for i in ok])
        eta = np.asarray(self.svc.models.eta, np.float64)
        y_scale = float(np.asarray(self.train_docs.y).std())
        w = reference.combine_weights(self.svc.models.train_mse, False)
        answer = max(
            float(np.abs(chains - np.einsum("rmt,mt->rm", zbar, eta)).max()),
            float(np.abs(comb - chains @ w).max())) / y_scale
        nums = {"zbar_gap": checks.zbar_gap(zbar, lens,
                                            conf["n_pred_samples"]),
                "answer_gap": answer}
        rng = np.random.default_rng(self.seed)
        pick = rng.choice(len(ok), min(SAMPLE, len(ok)), replace=False)
        pick = np.union1d(pick, [int(np.argmax(lens))])
        rows = np.array(ok)[pick]
        docs = Docs(*(a[rows] for a in self.request_docs))
        hp = reference.HP.of(conf)
        m = conf["n_chains"]
        k_ref = jax.random.fold_in(self.key, 99)
        with jax.default_matmul_precision("highest"):
            models = reference.train(
                jax.random.split(k_ref, m),
                reference.shards_of(self.train_docs, m), hp)
            _, yhat = reference.predict(
                jax.random.split(jax.random.fold_in(k_ref, 1), m), models,
                docs, hp)
        ref_out = reference.combine_weights(models.train_mse, False) \
            @ np.asarray(yhat, np.float64)
        nums["mse_excess"] = checks.mse_excess(comb[pick],
                                               np.asarray(docs.y), ref_out)
        return nums

    def context(self) -> dict:
        p95 = float(np.percentile(self.latency_s, 95) * 1e3) \
            if hasattr(self, "latency_s") else None
        return {"setup_parts": self.setup_parts,
                "notes": {"serve_p95_ms": p95,
                          "p95_limit_ms": self.traffic["p95_limit_ms"],
                          "host_pauses": getattr(self, "host_pauses",
                                                 None)}}


class HostPauses:
    """The longest host step of each kind in the window (seconds), and
    each garbage collection's count and longest pause by generation."""

    def __init__(self):
        self.longest = {"flush": 0.0, "submit": 0.0, "wait": 0.0}
        self.gc = {}
        self._gc_start = None

    def took(self, kind: str, t_start: float):
        self.longest[kind] = max(self.longest[kind],
                                 time.perf_counter() - t_start)

    def on_gc(self, phase: str, info: dict):
        if phase == "start":
            self._gc_start = time.perf_counter()
        elif self._gc_start is not None:
            n, worst = self.gc.get(info["generation"], (0, 0.0))
            self.gc[info["generation"]] = (
                n + 1, max(worst, time.perf_counter() - self._gc_start))
            self._gc_start = None

    def summary(self) -> dict:
        out = {f"longest_{k}_ms": v * 1e3 for k, v in self.longest.items()}
        for g, (n, worst) in sorted(self.gc.items()):
            out[f"gc{g}_count"] = n
            out[f"gc{g}_longest_ms"] = worst * 1e3
        return out


def due_times(seed: int, rate_per_s: float, seconds: float) -> np.ndarray:
    """round(rate × seconds) sorted uniform arrival times in [0, seconds)."""
    n = int(round(rate_per_s * seconds))
    rng = np.random.default_rng([seed, 0x5E5])
    return np.sort(rng.uniform(0.0, seconds, n))


def timings(t0, due, t_sub, service_s, ok, t_end):
    """(latency, lag, answer time) of each request, in seconds.  `due`
    is relative to the window start t0; `t_sub` is when the generator
    submitted; `service_s` is the service's own submit-to-answer time.
    An unanswered request counts as answered at t_end."""
    t_done = np.where(ok, t_sub + service_s, t_end)
    return t_done - (t0 + due), t_sub - (t0 + due), t_done
