"""Faults planted under the timed path, and the precision control, for
bench/calibrate.py and bench/tests: each is a context manager that
patches the program while a run is made, and each must turn that run's
`correct` false.

* `state_unchanged`: the Gibbs sweeps return the state they were given
  (training: the EM loop; serving: the prediction sweeps);
* `half_batch`: half of every batch is left out (training: the second
  half of each chain's shard; serving: the second half of the slots);
* `answer_altered`: one answer is changed where it is made;
* `control`: the plain reference, computed in bfloat16, in the
  program's place; it hands back float32, as the program does.

`control_draw` is read beside them and need not fail: the reference in
the program's place with only its draws (the weights p and their
prefix sums) in bfloat16, and its counts, sums and outputs in float32.
"""
from __future__ import annotations

import contextlib
from unittest import mock

import jax
import jax.numpy as jnp

from bench import reference
from bench.gen import Docs

KINDS = ("state_unchanged", "half_batch", "answer_altered", "control")
READINGS = ("control_draw",)


def _hp(cfg) -> reference.HP:
    return reference.HP(*(getattr(cfg, k) for k in reference.HP._fields))


def _flat_docs(corpus) -> Docs:
    """A plan's corpus (a padded Corpus or a BucketedCorpus in original
    order) as padded Docs."""
    buckets = getattr(corpus, "buckets", (corpus,))
    width = max(b.tokens.shape[-1] for b in buckets)
    pad = lambda a: jnp.pad(a, [(0, 0)] * (a.ndim - 1)
                            + [(0, width - a.shape[-1])])
    cat = lambda xs: jnp.concatenate(xs, axis=-2)
    return Docs(cat([pad(b.tokens) for b in buckets]),
                          cat([pad(b.mask) for b in buckets]),
                          jnp.concatenate([b.y for b in buckets], axis=-1))


def _model(models):
    from repro.core import SLDAModel
    return SLDAModel(phi=models.phi, eta=models.eta,
                     train_mse=models.train_mse, train_acc=models.train_acc)


def _ref_in_place(kind: str, dtype, draw_dtype=None):
    """Patches that put the reference, computed in `dtype` (its draws'
    weights in `draw_dtype`), in place of the program's samplers."""
    from repro.core import parallel
    from repro.core.plan import ExecutionPlan

    def train(key, shards, cfg):
        docs = _flat_docs(shards)
        m = docs.tokens.shape[0]
        return _model(reference.train(jax.random.split(key, m), docs,
                                      _hp(cfg), dtype, draw_dtype))

    def predict(key, models, corpus, cfg):
        m = models.eta.shape[0]
        return reference.predict(jax.random.split(key, m), models,
                                 _flat_docs(corpus), _hp(cfg), dtype,
                                 draw_dtype)[1]

    def predict_zbar(self, keys, models):
        return reference.predict(keys, models, _flat_docs(self.corpus),
                                 _hp(self.cfg), dtype, draw_dtype)[0]

    if kind == "serve":
        return [mock.patch.object(ExecutionPlan, "predict_zbar",
                                  predict_zbar)]
    return [mock.patch.object(parallel, "_train_chains_jit", train),
            mock.patch.object(parallel, "_predict_chains_jit", predict),
            mock.patch.object(parallel, "train_chains", train)]


@contextlib.contextmanager
def control(kind: str):
    """The reference in bfloat16 in place of the program's samplers."""
    with contextlib.ExitStack() as st:
        for p in _ref_in_place(kind, jnp.bfloat16):
            st.enter_context(p)
        yield


@contextlib.contextmanager
def control_draw(kind: str):
    """The float32 reference in the program's place, its draws' weights
    and prefix sums rounded to bfloat16."""
    with contextlib.ExitStack() as st:
        for p in _ref_in_place(kind, jnp.float32, jnp.bfloat16):
            st.enter_context(p)
        yield


@contextlib.contextmanager
def state_unchanged(kind: str):
    from repro.core.plan import ExecutionPlan
    if kind == "serve":
        def predict_zbar(self, keys, models):
            # z̄ of the initial random assignment: no sweep ran
            bc, cfg = self.corpus, self.cfg
            D, S = bc.n_docs, bc.ctr_stride
            ks = jax.vmap(jax.random.split)(keys)
            z0 = jax.vmap(lambda k: jax.random.randint(
                k, (D, S), 0, cfg.n_topics, jnp.int32))(ks[:, 0])
            docs = _flat_docs(bc)
            oh = jax.nn.one_hot(z0[..., :docs.tokens.shape[-1]],
                                cfg.n_topics) * docs.mask[None, ..., None]
            lens = jnp.maximum(docs.mask.sum(-1), 1.0)
            return oh.sum(-2) / lens[None, :, None]
        with mock.patch.object(ExecutionPlan, "predict_zbar", predict_zbar):
            yield
        return

    def train_em(self, k_sweeps, state0, **kw):
        return state0
    with mock.patch.object(ExecutionPlan, "train_em", train_em):
        yield


@contextlib.contextmanager
def half_batch(kind: str):
    from repro.core import parallel
    if kind == "serve":
        from repro.serving.slda_service import SLDAPredictionService
        orig = SLDAPredictionService._build_schedule

        def build(self, placed):
            bc, meta = orig(self, placed)
            real = [i for i, m in enumerate(meta) if m is not None]
            drop = set(real[len(real) // 2:]) if len(real) > 1 else set()
            o, buckets = 0, []
            for b in bc.buckets:
                keep = jnp.asarray([0.0 if o + i in drop else 1.0
                                    for i in range(b.tokens.shape[0])])
                buckets.append(type(b)(tokens=b.tokens,
                                       mask=b.mask * keep[:, None], y=b.y))
                o += b.tokens.shape[0]
            return type(bc)(buckets=tuple(buckets), perm=bc.perm,
                            inv_perm=bc.inv_perm,
                            ctr_stride=bc.ctr_stride), meta
        with mock.patch.object(SLDAPredictionService, "_build_schedule",
                               build):
            yield
        return
    orig = parallel.partition

    def partition(corpus, m):
        shards = orig(corpus, m)
        d = shards.mask.shape[1]
        keep = (jnp.arange(d) < d // 2).astype(shards.mask.dtype)
        return type(shards)(tokens=shards.tokens,
                            mask=shards.mask * keep[None, :, None],
                            y=shards.y)
    with mock.patch.object(parallel, "partition", partition):
        yield


@contextlib.contextmanager
def answer_altered(kind: str):
    from repro.core import parallel
    if kind == "serve":
        from repro.serving.slda_service import SLDAPredictionService
        orig = SLDAPredictionService.flush

        def flush(self):
            done = orig(self)
            if done:
                self._results[done[0]].yhat += 1.0
            return done
        with mock.patch.object(SLDAPredictionService, "flush", flush):
            yield
        return
    if kind == "weighted":
        orig = parallel.ALGORITHMS["weighted"]

        def weighted(*a, **kw):
            return orig(*a, **kw).at[0].add(1.0)
        with mock.patch.dict(parallel.ALGORITHMS, {"weighted": weighted}):
            yield
        return
    orig = parallel.train_chains

    def train_chains(*a, **kw):
        models = orig(*a, **kw)
        # the largest count of chain 0, topic 0 doubled in φ̂
        w = jnp.argmax(models.phi[0, 0])
        return models.__class__(phi=models.phi.at[0, 0, w].multiply(2.0),
                                eta=models.eta, train_mse=models.train_mse,
                                train_acc=models.train_acc)
    with mock.patch.object(parallel, "train_chains", train_chains):
        yield


@contextlib.contextmanager
def planted(fault: str, kind: str):
    """The context manager of `fault` for a cell of `kind` ("weighted",
    "train_chains" or "serve").  JAX's in-memory caches are cleared on
    entry and exit: a jitted function traced before the patch would
    otherwise run its unpatched program."""
    jax.clear_caches()
    try:
        with {"state_unchanged": state_unchanged, "half_batch": half_batch,
              "answer_altered": answer_altered, "control": control,
              "control_draw": control_draw}[fault](kind):
            yield
    finally:
        jax.clear_caches()
