"""The work a cell asks of the chip, counted from its configuration and
its corpus lengths, never from the program.

A draw is one token's topic resampled in one sweep; padding is not a
draw.  The operations and bytes are those of the sampler's arithmetic,
not of any implementation of it: the prefix sum over T topics counts as
T additions whether a kernel runs it as a cumulative sum or as a
[T, T] triangular product.  So a roofline share reads the same work
whatever computes it.
"""
from __future__ import annotations

# Operations per topic of one training draw (the supervised collapsed
# conditional, Eq. 1 of arXiv:1708.03052 with the Gaussian response):
#   (n_dt + α) 1, (n_tw + β) 1, (n_t + Wβ) 1, the division 1,
#   the product of the three 2, μ_t = (s + η_t)/N_d 2, (y − μ_t)² 2,
#   scaling by −1/2ρ 1, the exponential 1, the product with it 1,
#   the prefix sum 1, the comparison with the uniform 1.
TRAIN_OPS_PER_TOPIC = 15
# ... of one prediction draw (Eq. 4, φ̂ frozen):
#   (n_dt + α) 1, the product with φ̂_tw 1, the prefix sum 1,
#   the comparison 1.
PREDICT_OPS_PER_TOPIC = 4
# Bytes a draw must move at the least: the word's row of T float32
# counts (training) or of φ̂ (prediction), and the token's word id, mask,
# uniform, old and new topic (4 bytes each).  A training draw also
# feeds the count rebuild at the end of its sweep: its word id and topic
# are read again and one count is written (12 bytes).
BYTES_PER_ROW_ENTRY = 4
TOKEN_BYTES = 20
REBUILD_BYTES = 12


def real_tokens(lengths) -> int:
    return int(sum(int(n) for n in lengths))


def fit_draws(entry: str, conf: dict, train_lengths, test_lengths) -> dict:
    """Draws of one fit of `entry` ({"train": n, "predict": n}).

    "weighted": the chains together sweep the training set `n_iters`
    times, then each of the M chains predicts the test set and the whole
    training set (burn-in and sample sweeps).  "train_chains": the
    training sweeps alone."""
    n_tr, n_te = real_tokens(train_lengths), real_tokens(test_lengths)
    m = conf["n_chains"]
    sweeps = conf["n_pred_burnin"] + conf["n_pred_samples"]
    train = n_tr * conf["n_iters"]
    predict = {"weighted": m * (n_te + n_tr) * sweeps,
               "train_chains": 0}[entry]
    return {"train": train, "predict": predict}


def serve_draws(conf: dict, doc_lengths) -> int:
    """Draws of serving these documents: every chain predicts each."""
    sweeps = conf["n_pred_burnin"] + conf["n_pred_samples"]
    return conf["n_chains"] * real_tokens(doc_lengths) * sweeps


def ops(draws: dict, n_topics: int) -> float:
    return float(draws.get("train", 0) * TRAIN_OPS_PER_TOPIC * n_topics
                 + draws.get("predict", 0) * PREDICT_OPS_PER_TOPIC
                 * n_topics)


def bytes_moved(draws: dict, n_topics: int) -> float:
    row = n_topics * BYTES_PER_ROW_ENTRY + TOKEN_BYTES
    return float(draws.get("train", 0) * (row + REBUILD_BYTES)
                 + draws.get("predict", 0) * row)


def roofline(draws: dict, n_topics: int, seconds: float,
             peaks: dict) -> tuple[float, str]:
    """(share of the roofline in %, the bound that applies) of doing
    `draws` in `seconds` of device time."""
    t_ops = ops(draws, n_topics) / peaks["flops_per_s"]
    t_bytes = bytes_moved(draws, n_topics) / peaks["hbm_bytes_per_s"]
    bound = "bytes" if t_bytes >= t_ops else "ops"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
