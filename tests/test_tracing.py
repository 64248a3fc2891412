"""The program's names for its own work (DESIGN.md §Tracing): the named
scopes of the jitted programs, kept as HLO `op_name` metadata on every
executor, and the service's host spans in a profiler trace."""
import dataclasses
import functools
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Corpus, SLDAConfig, SLDAModel, apply_count_deltas,
                        build_schedule, partition)
from repro.core.parallel import predict_chains, train_chains
from repro.serving import ServiceConfig, SLDAPredictionService

CFG = SLDAConfig(n_topics=4, vocab_size=24, n_iters=2, rho=0.25,
                 n_pred_burnin=1, n_pred_samples=1, count_rebuild_every=2)
TRAIN_SCOPES = {"gibbs_sweep", "count_refresh", "rebuild", "eta_solve"}


def _corpus(d=16, n=12, seed=0):
    rng = np.random.default_rng(seed)
    lens = rng.integers(2, n + 1, d)
    mask = (np.arange(n)[None, :] < lens[:, None]).astype(np.float32)
    return Corpus(tokens=jnp.asarray(rng.integers(0, CFG.vocab_size, (d, n)),
                                     jnp.int32),
                  mask=jnp.asarray(mask),
                  y=jnp.asarray(rng.normal(size=d), jnp.float32))


def _models(m=2, seed=1):
    rng = np.random.default_rng(seed)
    phi = rng.random((m, CFG.n_topics, CFG.vocab_size)) + 0.1
    return SLDAModel(phi=jnp.asarray(phi / phi.sum(-1, keepdims=True),
                                     jnp.float32),
                     eta=jnp.asarray(rng.normal(size=(m, CFG.n_topics)),
                                     jnp.float32),
                     train_mse=jnp.ones((m,), jnp.float32),
                     train_acc=jnp.ones((m,), jnp.float32))


def scopes_of(hlo_text: str) -> set:
    """Every name in the op_name metadata, transformation wrappers
    removed ("vmap(dense)" → "dense")."""
    out = set()
    for path in re.findall(r'op_name="([^"]*)"', hlo_text):
        out |= {re.sub(r"^(?:[\w.-]*\()*|\)*$", "", c)
                for c in path.split("/")}
    return out


@pytest.mark.parametrize("spl,buckets,compiled", [
    (1, 0, True),       # seed path, compiled: the names survive XLA
    (2, 0, False),      # fused launches, blocks executor
    (2, 2, False),      # fused launches, stair executor
], ids=["seed", "blocks", "stair"])
def test_programs_carry_their_scopes(spl, buckets, compiled):
    cfg = dataclasses.replace(CFG, sweeps_per_launch=spl,
                              length_buckets=buckets)
    corpus = _corpus()
    shards = build_schedule(partition(corpus, 2), cfg)
    low = jax.jit(train_chains, static_argnums=(2,)).lower(
        jax.random.PRNGKey(0), shards, cfg)
    text = (low.compile().as_text() if compiled
            else low.as_text(dialect="hlo", debug_info=True))
    assert TRAIN_SCOPES <= scopes_of(text)
    low = jax.jit(predict_chains, static_argnums=(3,)).lower(
        jax.random.PRNGKey(0), _models(), build_schedule(corpus, cfg), cfg)
    assert "predict_sweeps" in scopes_of(
        low.as_text(dialect="hlo", debug_info=True))


def _ops_under(hlo_text: str, scope: str) -> list:
    """Opcodes of the instructions whose op_name path holds `scope`."""
    ops = []
    for line in hlo_text.splitlines():
        path = re.search(r'op_name="([^"]*)"', line)
        op = re.search(r"=\s+(?:\([^)]*\)|\S+)\s+([a-z][\w-]*)\(", line)
        if path and op and scope in scopes_of(path.group(0)):
            ops.append(op.group(1))
    return ops


@pytest.mark.parametrize("spl,buckets", [(1, 0), (2, 0), (2, 2)],
                         ids=["seed", "blocks", "stair"])
def test_count_refresh_is_one_scatter_per_bucket(spl, buckets):
    """The refresh is one recount: a scatter per bucket and no branch —
    no compacted or dense delta form beside it, nothing chosen at run
    time.  The configurations have no remainder launch, so the program
    traces the EM boundary once."""
    cfg = dataclasses.replace(CFG, sweeps_per_launch=spl,
                              length_buckets=buckets)
    assert cfg.n_iters % spl == 0
    shards = build_schedule(partition(_corpus(), 2), cfg)
    text = jax.jit(train_chains, static_argnums=(2,)).lower(
        jax.random.PRNGKey(0), shards, cfg).as_text(dialect="hlo",
                                                     debug_info=True)
    ops = _ops_under(text, "count_refresh")
    assert ops.count("scatter") == len(shards.buckets)
    assert "conditional" not in ops


def test_count_delta_branches_are_scoped():
    c = _corpus()
    z = jnp.zeros(c.tokens.shape, jnp.int32)
    ntw = jnp.zeros((CFG.n_topics, CFG.vocab_size), jnp.float32)
    low = jax.jit(functools.partial(apply_count_deltas, cap=8)).lower(
        ntw, ntw.sum(-1), c.tokens, c.mask, z, z + 1)
    assert {"compact", "dense"} <= scopes_of(
        low.as_text(dialect="hlo", debug_info=True))


def _service():
    return SLDAPredictionService(
        _models(), CFG, ServiceConfig(max_doc_len=12, batch_docs=4),
        key=jax.random.PRNGKey(3))


def _spans(log_dir):
    from jax.profiler import ProfileData
    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0]
    return sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats))
                   for p in ProfileData.from_file(path).planes
                   for line in p.lines for e in line.events
                   if e.name.startswith("slda.")), key=lambda s: s[1])


def test_flush_records_its_spans(tmp_path):
    svc = _service()
    docs = [np.arange(1 + i % 9) % CFG.vocab_size for i in range(4)]
    for d in docs:                          # compile outside the trace
        svc.submit(d + 1)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        assert svc.flush() == []            # empty queue: no span
        rids = [svc.submit(d) for d in docs]    # the 4th auto-flushes
    finally:
        jax.profiler.stop_trace()
    spans = _spans(tmp_path)
    names = [s[0] for s in spans]
    assert names.count("slda.serve.submit") == 4
    assert [s[3]["req_id"] for s in spans
            if s[0] == "slda.serve.submit"] == rids
    flush = {s[0]: s for s in spans if s[0] != "slda.serve.submit"}
    assert sorted(flush) == ["slda.serve.device", "slda.serve.pack",
                             "slda.serve.publish"]
    assert all(s[3]["batch"] == 1 for s in flush.values())
    assert flush["slda.serve.pack"][3]["docs"] == 4
    # pack, device, publish follow one another inside the last submit
    last = [s for s in spans if s[0] == "slda.serve.submit"][-1]
    seq = [flush[n] for n in ("slda.serve.pack", "slda.serve.device",
                              "slda.serve.publish")]
    assert last[1] <= seq[0][1] and seq[-1][2] <= last[2]
    assert all(a[2] <= b[1] for a, b in zip(seq, seq[1:]))
    assert all(svc.result(r).status == "ok" for r in rids)


def test_dispatch_program_carries_its_scopes():
    svc = _service()
    bc, _ = svc._build_schedule([[(0, np.arange(3), 0.0, np.inf)]])
    from repro.core import build_plan
    plan = build_plan(bc, svc.cfg, svc.backend)
    fn = svc._dispatch_fn(plan.cache_key())
    low = fn.lower(jax.random.split(jax.random.PRNGKey(0), 2), svc.models,
                   plan, svc.chain_weights)
    assert {"predict_sweeps", "combine"} <= scopes_of(
        low.as_text(dialect="hlo", debug_info=True))
