"""Compile the sLDA Pallas kernels for a described TPU v5e, without a chip.

The TPU compiler is installed with JAX and compiles for a topology that
is described rather than attached, so these tests catch what interpret
mode cannot: lowerings Mosaic refuses (uint32→f32 casts, gathers,
dynamic lane indexing) and kernels that overflow VMEM/SMEM.  Shapes are
the paper's experiments at full width: MD&A (W=4238, N=120) and IMDB
(W=8000, N=150), T=32, M=8 chains, the documents of one training shard
(375 → 384 rows) and the full Weighted-Average prediction set (4216).

The kernels are called directly: `jax.default_backend()` still says CPU
here, so the ops-level wrappers would pick interpret mode.  The
topology is described inside a fixture, never at import, so the test
workers all collect the same tests and only the one that runs them
loads the TPU library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.slda_gibbs import slda_gibbs_sweep_pallas
from repro.kernels.slda_predict import (slda_predict_sweeps_chains_pallas,
                                        slda_predict_sweeps_pallas)
from repro.kernels.slda_train import (slda_train_sweeps_chains_pallas,
                                      slda_train_sweeps_pallas)

T, M = 32, 8
D_TRAIN, D_PRED = 384, 4216
WIDTHS = {"mdna": (4238, 120), "imdb": (8000, 150)}
HYPER = dict(alpha=0.1, beta=0.01, rho=0.5)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep these out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _args(one_chip, *shapes):
    return [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]


def _chain_args(one_chip, d, n, w):
    i32, f32 = jnp.int32, jnp.float32
    return _args(one_chip, ((M, d, n), i32), ((M, d, n), f32), ((M, d), i32),
                 ((M, d, n), i32), ((M, d, T), f32), ((M, d), f32),
                 ((M, d), f32), ((M, w, T), f32), ((M, T), f32),
                 ((M, T), f32))


def _case(kind, one_chip, w, n):
    """(kernel with its static options bound, argument shapes)."""
    i32, f32 = jnp.int32, jnp.float32
    if kind.startswith("gibbs"):
        # the spl=1 plan route vmaps the single-chain sweep over chains
        lead = (M,) if kind == "gibbs_chains" else ()
        fn = functools.partial(slda_gibbs_sweep_pallas, doc_block=8,
                               interpret=False, **HYPER)
        return ((jax.vmap(fn) if lead else fn),
                _args(one_chip, *[(lead + shape, dt) for shape, dt in (
                    ((D_TRAIN, n), i32), ((D_TRAIN, n), f32),
                    ((D_TRAIN, n), f32), ((D_TRAIN, n), i32),
                    ((D_TRAIN, T), f32), ((D_TRAIN,), f32),
                    ((D_TRAIN,), f32), ((w, T), f32), ((T,), f32),
                    ((T,), f32))]))
    if kind.startswith("train_chains"):
        spl = 1 if kind == "train_chains_spl1" else 8
        return (functools.partial(
            slda_train_sweeps_chains_pallas, n_sweeps=spl, doc_block=128,
            product_form=spl > 1, tpu_prng=kind.endswith("prng"),
            interpret=False, **HYPER), _chain_args(one_chip, D_TRAIN, n, w))
    if kind == "train_single_spl8":
        return (functools.partial(slda_train_sweeps_pallas, n_sweeps=8,
                                  doc_block=128, product_form=True,
                                  interpret=False, **HYPER),
                _args(one_chip, ((D_TRAIN, n), i32), ((D_TRAIN, n), f32),
                      ((D_TRAIN,), i32), ((D_TRAIN, n), i32),
                      ((D_TRAIN, T), f32), ((D_TRAIN,), f32),
                      ((D_TRAIN,), f32), ((w, T), f32), ((T,), f32),
                      ((T,), f32)))
    pred = dict(alpha=0.1, n_burnin=15, n_samples=10, doc_block=8,
                interpret=False)
    if kind.startswith("predict_chains"):
        return (functools.partial(slda_predict_sweeps_chains_pallas,
                                  tpu_prng=kind.endswith("prng"), **pred),
                _args(one_chip, ((D_PRED, n), i32), ((D_PRED, n), f32),
                      ((M, D_PRED), i32), ((M, D_PRED, n), i32),
                      ((M, D_PRED, T), f32), ((M, w, T), f32)))
    assert kind == "predict_single"
    return (functools.partial(slda_predict_sweeps_pallas, **pred),
            _args(one_chip, ((D_PRED, n), i32), ((D_PRED, n), f32),
                  ((D_PRED,), i32), ((D_PRED, n), i32), ((D_PRED, T), f32),
                  ((w, T), f32)))


@pytest.mark.parametrize("corpus", sorted(WIDTHS))
@pytest.mark.parametrize("kind", [
    "gibbs", "gibbs_chains", "train_chains_spl1", "train_chains_spl8",
    "train_chains_spl8_prng", "train_single_spl8", "predict_chains",
    "predict_chains_prng", "predict_single"])
def test_kernel_compiles_for_v5e(kind, corpus, one_chip):
    w, n = WIDTHS[corpus]
    fn, args = _case(kind, one_chip, w, n)
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_sparse_mode_is_refused_when_compiled(one_chip):
    """The sparse draw has no compiled kernel: asking for one fails
    loudly instead of lowering (or silently interpreting)."""
    w, n = WIDTHS["mdna"]
    fn, args = _case("predict_chains", one_chip, w, n)
    with pytest.raises(NotImplementedError, match="sparse"):
        jax.jit(functools.partial(fn, sampler_mode="sparse")).lower(*args)


def test_shard_runner_compiles_for_four_v5e(topo, monkeypatch):
    """The multi-device runner's Weighted Average program at the MD&A
    cell's size on a described 2x2 mesh, 2 chains a chip, the Pallas
    kernels compiled: the one collective is the all-gather."""
    import dataclasses
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import SLDA_MDNA
    from repro.core import Corpus
    from repro.kernels import ops
    from repro.launch import slda_parallel
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices[:4]), ("chains",))
    rep = NamedSharding(mesh, P())
    w, n = WIDTHS["mdna"]
    d_tr, d_te = 3000, 1216
    cfg = dataclasses.replace(SLDA_MDNA, use_pallas=True)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=rep)
    corpus = lambda *lead: Corpus(sds(lead + (n,), jnp.int32),
                                  sds(lead + (n,), jnp.float32),
                                  sds(lead, jnp.float32))
    hlo = slda_parallel._shard_chains_jit.lower(
        sds((2,), jnp.uint32), corpus(M, d_tr // M), corpus(d_te + d_tr),
        sds((d_tr,), jnp.float32), None, cfg=cfg, mesh=mesh, axis="chains",
        cpd=M // 4, rule="weighted", n_test=d_te,
        quarantine=True).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert re.findall(r"\s((?:all-gather|all-reduce|all-to-all|"
                      r"collective-permute|reduce-scatter)(?:-start)?)\(",
                      hlo) == ["all-gather"]
