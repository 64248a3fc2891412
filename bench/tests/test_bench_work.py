"""bench/work.py against a hand count on a 3-document corpus."""
import pytest

from bench import work

CONF = {"n_chains": 2, "n_iters": 4, "n_pred_burnin": 2,
        "n_pred_samples": 3}
TRAIN = [3, 5]          # two training documents: 8 real tokens
TEST = [4]              # one test document: 4 real tokens


@pytest.mark.parametrize("entry,train,predict", [
    # 8 tokens × 4 sweeps; 2 chains × (4 + 8) tokens × 5 sweeps
    ("weighted", 32, 120),
    ("train_chains", 32, 0),
])
def test_fit_draws_by_hand(entry, train, predict):
    assert work.fit_draws(entry, CONF, TRAIN, TEST) == {
        "train": train, "predict": predict}


def test_serve_draws_by_hand():
    assert work.serve_draws(CONF, [3, 5, 4]) == 2 * 12 * 5


def test_ops_and_bytes_by_hand():
    draws = {"train": 10, "predict": 20}
    assert work.ops(draws, 4) == 10 * 15 * 4 + 20 * 4 * 4
    row = 4 * 4 + 20
    assert work.bytes_moved(draws, 4) == 10 * (row + 12) + 20 * row


def test_roofline_names_its_bound():
    peaks = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    draws = {"predict": 1000}
    share, bound = work.roofline(draws, 32, 1.0, peaks)
    assert bound == "bytes"
    assert share == pytest.approx(100 * 1000 * (32 * 4 + 20) / 1e9)
    share, bound = work.roofline(draws, 32, 1.0,
                                 {"flops_per_s": 1e3, "hbm_bytes_per_s": 1e12})
    assert bound == "ops"
    assert share == pytest.approx(100 * 1000 * 4 * 32 / 1e3)
