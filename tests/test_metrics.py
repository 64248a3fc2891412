"""Metrics substrate tests: logger restart semantics + ensemble health."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.metrics import MetricLogger, chain_divergence, ensemble_health


def test_logger_roundtrip_and_restart(tmp_path):
    path = str(tmp_path / "m.jsonl")
    log = MetricLogger(path)
    for s in range(5):
        log.log(s, loss=[1.0 / (s + 1), 2.0], lr=1e-3)
    # simulate restart from step 3: steps 3,4 re-logged with new values
    log2 = MetricLogger(path)
    log2.log(3, loss=[9.0, 9.0], lr=1e-3)
    rows = log2.read()
    assert [r["step"] for r in rows] == [0, 1, 2, 3, 4]
    assert rows[3]["loss"] == [9.0, 9.0]       # superseded


def test_logger_survives_partial_line(tmp_path):
    path = str(tmp_path / "m.jsonl")
    log = MetricLogger(path)
    log.log(0, loss=1.0)
    with open(path, "a") as f:
        f.write('{"step": 1, "loss"')           # crash mid-write
    assert [r["step"] for r in log.read()] == [0]


def test_chain_divergence_zero_for_identical():
    logits = jnp.broadcast_to(jnp.arange(8.0), (3, 4, 8))
    kl = chain_divergence(logits)
    np.testing.assert_allclose(np.asarray(kl), 0.0, atol=1e-5)


def test_chain_divergence_positive_for_different():
    k = jax.random.PRNGKey(0)
    logits = jax.random.normal(k, (3, 4, 16)) * 3
    kl = np.asarray(chain_divergence(logits))
    off = kl[~np.eye(3, dtype=bool)]
    assert (off > 0.01).all()
    np.testing.assert_allclose(kl, kl.T, atol=1e-5)


def test_ensemble_health_drops_diverged_chain():
    loss = jnp.asarray([2.30, 2.28, 2.31, 45.0])      # chain 3 diverged
    alive, report = ensemble_health(loss)
    assert alive.tolist() == [1.0, 1.0, 1.0, 0.0]


def test_ensemble_health_drops_nan_chain():
    loss = jnp.asarray([2.3, jnp.nan, 2.31])
    alive, _ = ensemble_health(loss)
    assert alive.tolist() == [1.0, 0.0, 1.0]


def test_ensemble_health_flags_collapse():
    loss = jnp.asarray([2.3, 2.3])
    same = jnp.broadcast_to(jnp.arange(16.0), (2, 4, 16))
    _, report = ensemble_health(loss, logits=same)
    assert report["collapsed"]
    diff = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 16)) * 3
    _, report = ensemble_health(loss, logits=diff)
    assert not report["collapsed"]
