"""The multi-device runner's Weighted Average (Eqs. 8-9 with every chain
re-predicting the full training set) and what it hands out.

Everything runs in one subprocess on four forced host devices (the
device count is locked at JAX's first use), at a small size:

* the per-chain models and ŷ of `return_chains=True` are bit-equal, on
  the jnp route, to `train_chains_keyed` / `predict_chains_keyed` run
  slice by slice on one device with the same per-chain keys, and the
  combined ŷ is `_combine_weighted` of the handed-out ŷ;
* 4-device and 1-device meshes of the same M chains give bit-equal ŷ
  and models, on the jnp route and on the Pallas route (interpret mode,
  at fewer sweeps): the kernels' chain grid is 2 on each of four devices
  against 8 on one;
* the benchmark's structural checks hold on the handed-out outputs, and
  the held-out MSE stays near the plain float32 reference's;
* the compiled program holds no collective but the one all-gather, on
  the jnp and the Pallas-interpret routes, and carries the named scopes
  `chain_gather` and `combine`.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROG = textwrap.dedent("""
    import json, os, re, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [{root!r}, os.path.join({root!r}, "src")]
    import dataclasses
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from bench import checks, reference
    from bench.gen import Docs
    from repro.core import SLDAConfig, parallel, partition
    from repro.core.types import _concat_corpora
    from repro.data import make_slda_corpus, train_test_split
    from repro.launch.slda_parallel import parallel_slda_shard_map

    M, CPD = 8, 2
    cfg = SLDAConfig(n_topics=6, vocab_size=120, n_iters=12,
                     n_pred_burnin=4, n_pred_samples=4, rho=0.25)
    corpus, _ = make_slda_corpus(jax.random.PRNGKey(0), 240, 120, 6, 24,
                                 rho=0.25)
    train, test = train_test_split(corpus, 160)
    n_te = test.n_docs
    key = jax.random.PRNGKey(11)
    out = {{}}

    def mesh_of(n):
        return Mesh(np.array(jax.devices()[:n]), ("chains",))

    def fit(n, c=cfg):
        mesh = mesh_of(n)
        rep = NamedSharding(mesh, P())
        return parallel_slda_shard_map(
            key, jax.device_put(train, rep), jax.device_put(test, rep), c,
            mesh, axis="chains", rule="weighted", chains_per_device=M // n,
            return_chains=True)

    f4, f1 = fit(4), fit(1)
    # (a) slice by slice on one device, the runner's per-chain keys
    keys = jax.vmap(lambda g: jax.random.fold_in(key, g))(jnp.arange(M))
    ks = jax.vmap(jax.random.split)(keys)
    shards = partition(train, M)
    both = _concat_corpora(test, train)
    tr = jax.jit(parallel.train_chains_keyed, static_argnums=2)
    pr = jax.jit(parallel.predict_chains_keyed, static_argnums=3)
    equal_models, equal_yhat = True, True
    for s in range(M // CPD):
        sl = slice(s * CPD, (s + 1) * CPD)
        _, models = tr(ks[sl, 0], jax.tree.map(lambda x: x[sl], shards), cfg)
        yh = pr(ks[sl, 1], models, both, cfg)
        equal_models &= all(
            np.array_equal(a, np.asarray(b)[sl]) for a, b in
            zip(jax.tree.leaves(models), jax.tree.leaves(f4.models)))
        equal_yhat &= np.array_equal(yh, np.asarray(f4.yhat_chains)[sl])
    out["models_equal"], out["yhat_chains_equal"] = equal_models, equal_yhat
    yc = jnp.asarray(np.asarray(f4.yhat_chains))
    comb = parallel._combine_weighted(yc[:, :n_te], yc[:, n_te:], train.y,
                                      cfg, f4.alive)
    out["combine_rel_diff"] = float(np.max(np.abs(comb - f4.yhat)
                                           / np.abs(comb)))
    out["shapes"] = [list(f4.yhat.shape), list(f4.yhat_chains.shape),
                     list(f4.models.phi.shape)]
    out["alive"] = np.asarray(f4.alive).tolist()
    out["sharded"] = [len(f4.models.phi.addressable_shards),
                      list(f4.models.phi.addressable_shards[0].data.shape)]
    # (b) layouts
    out["layouts_equal"] = bool(np.array_equal(f4.yhat, f1.yhat)
                                and np.array_equal(f4.yhat_chains,
                                                   f1.yhat_chains))
    def same_fit(a, b):
        return all(np.array_equal(x, y) for x, y in zip(
            jax.tree.leaves((a.yhat, a.yhat_chains, a.models)),
            jax.tree.leaves((b.yhat, b.yhat_chains, b.models))))
    cp = dataclasses.replace(cfg, use_pallas=True, n_iters=3,
                             n_pred_burnin=1, n_pred_samples=1)
    out["layouts_equal_pallas"] = bool(same_fit(fit(4, cp), fit(1, cp)))
    # (c) the benchmark's checks and the float32 reference
    wc = checks.word_counts(train.tokens, train.mask, M, cfg.vocab_size)
    out["count_gap"] = checks.count_gap(f4.models.phi, wc, cfg.beta)
    y_tr = np.asarray(train.y, np.float64)
    w = reference.weights_from_predictions(
        np.asarray(f4.yhat_chains, np.float64)[:, n_te:], y_tr, False)
    y_te = np.asarray(test.y, np.float64)
    out["combine_gap"] = checks.combine_gap(
        f4.yhat, np.asarray(f4.yhat_chains)[:, :n_te], w, y_te.std())
    hp = reference.HP(*(getattr(cfg, k) for k in reference.HP._fields))
    with jax.default_matmul_precision("highest"):
        ref = reference.weighted_average(
            jax.random.PRNGKey(99), Docs(train.tokens, train.mask, train.y),
            Docs(test.tokens, test.mask, test.y), hp, M, False)
    out["mse_excess"] = checks.mse_excess(f4.yhat, y_te, ref["out"])

    # (d), (e) the compiled and the lowered program
    def program(c):
        mesh = mesh_of(4)
        rep = NamedSharding(mesh, P())
        tr_, te_ = jax.device_put(train, rep), jax.device_put(test, rep)
        lowered = jax.jit(lambda k: parallel_slda_shard_map(
            k, tr_, te_, c, mesh, axis="chains", rule="weighted",
            chains_per_device=CPD)).lower(key)
        hlo = lowered.compile().as_text()
        ops = re.findall(r"\\s((?:all-gather|all-reduce|all-to-all|"
                         r"collective-permute|reduce-scatter|"
                         r"collective-broadcast)(?:-start)?)\\(", hlo)
        return ops, lowered.as_text(debug_info=True)
    out["collectives"], text = program(cfg)
    out["scopes"] = {{s: s in text for s in ("chain_gather", "combine")}}
    out["collectives_pallas"], _ = program(dataclasses.replace(
        cfg, use_pallas=True, n_iters=3, n_pred_burnin=1,
        n_pred_samples=1))
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def res():
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", PROG.format(root=ROOT)],
                       capture_output=True, text=True, timeout=900, env=env,
                       cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_handed_out_chains_are_the_single_device_entries(res):
    assert res["models_equal"] and res["yhat_chains_equal"]


def test_handed_out_shapes_and_sharding(res):
    # ŷ [D_test]; per-chain ŷ over test ‖ train; φ̂ [M, T, W], two chains
    # on each of the four devices
    assert res["shapes"] == [[80], [8, 240], [8, 6, 120]]
    assert res["sharded"] == [4, [2, 6, 120]]
    assert res["alive"] == [1.0] * 8


def test_combined_yhat_is_combine_weighted_of_the_chains(res):
    # the same float32 arithmetic, fused inside the program rather than
    # op by op: equal to a few units in the last place
    assert res["combine_rel_diff"] <= 1e-6


def test_four_and_one_device_meshes_agree_bitwise(res):
    assert res["layouts_equal"]


def test_four_and_one_device_meshes_agree_bitwise_on_pallas(res):
    assert res["layouts_equal_pallas"]


@pytest.mark.parametrize("check,limit", [("count_gap", 1e-4),
                                         ("combine_gap", 1e-5)])
def test_benchmark_structure_checks_hold(res, check, limit):
    # whole counts read back from φ̂ (float32 rounding only), and Eqs.
    # 8-9 recomputed in float64 from the handed-out ŷ
    assert res[check] <= limit


def test_heldout_mse_near_the_float32_reference(res):
    # The reference fits its own 8 chains from its own key: at 160
    # training documents the two ensembles' held-out MSE differ by
    # sampling noise alone, well inside the 0.3 the chip cell allows,
    # where a fit that never sweeps reads (MSE - MSE_ref) / MSE_ref > 0.8.
    assert -0.3 <= res["mse_excess"] <= 0.3


@pytest.mark.parametrize("route", ["collectives", "collectives_pallas"])
def test_the_one_collective_is_the_gather(res, route):
    assert res[route] == ["all-gather"]


@pytest.mark.parametrize("scope", ["chain_gather", "combine"])
def test_program_carries_the_scope(res, scope):
    assert res["scopes"][scope]
