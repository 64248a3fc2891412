"""The numbers that decide `correct`, each against its limit.

Gibbs sampling draws at random, so no reference reproduces the
program's draws.  Two kinds of number are compared instead:

* exact structure of what the program produced, read against the
  corpus the benchmark made:
  - `count_gap`: every chain's φ̂ = (n_tw + β)/(n_t + Wβ) must come from
    whole counts that add up, word by word, to the words of that
    chain's shard, and topic by topic to the denominator n_t.  The
    counts are read back from φ̂ (n_t + Wβ is β over the smallest entry
    of a topic's row, which belongs to a word with no count) and the
    number is the largest of the farthest count from a whole number,
    the largest word-count mismatch and the largest gap between a
    topic's counts and its denominator;
  - `zbar_gap`: a served z̄ is an average of `n_pred_samples` topic
    count vectors of a document of length L, so z̄·S·L is whole and adds
    up to S·L; the number is the farthest it lies from that;
  - `combine_gap`, `answer_gap`: the combined ŷ is Eqs. 8-9 of the
    per-chain ŷ, and (served) each chain's ŷ is η̂ᵀz̄; the gap, over the
    label's standard deviation;
* quality against the plain reference (bench/reference.py) on the same
  documents: `mse_excess` = (MSE − MSE_ref) / MSE_ref of the held-out
  predictions against the true labels, where the reference fits its own
  chains in float32 from its own key.
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def count_gap(phi, word_counts, beta: float) -> float:
    """phi [M, T, W], word_counts [M, W] of each chain's shard."""
    phi = np.asarray(phi, np.float64)
    scale = beta / phi.min(axis=-1, keepdims=True)        # n_t + Wβ
    counts = phi * scale - beta
    whole = np.rint(counts)
    frac = float(np.abs(counts - whole).max())
    mismatch = float(np.abs(whole.sum(axis=1)
                            - np.asarray(word_counts, np.float64)).max())
    total = float(np.abs(whole.sum(-1, keepdims=True)
                         + phi.shape[-1] * beta - scale).max())
    return max(frac, mismatch, total)


def zbar_gap(zbar, lengths, n_samples: int) -> float:
    """zbar [R, M, T] of R answers, lengths [R]."""
    zbar = np.asarray(zbar, np.float64)
    sl = n_samples * np.asarray(lengths, np.float64)[:, None, None]
    c = zbar * sl
    whole = np.rint(c)
    return max(float(np.abs(c - whole).max()),
               float(np.abs(whole.sum(-1) - sl[..., 0]).max()))


def combine_gap(out, yhat_test, weights, y_scale: float) -> float:
    ref = np.asarray(weights, np.float64) @ np.asarray(yhat_test, np.float64)
    return float(np.abs(np.asarray(out, np.float64) - ref).max()) / y_scale


def mse(yhat, y) -> float:
    return float(np.mean((np.asarray(yhat, np.float64)
                          - np.asarray(y, np.float64)) ** 2))


def mse_excess(yhat, y, yhat_ref) -> float:
    ref = mse(yhat_ref, y)
    return (mse(yhat, y) - ref) / ref


def word_counts(tokens, mask, n_chains: int, vocab_size: int) -> np.ndarray:
    """[M, W] word counts of the contiguous shards of a [D, N] corpus."""
    tokens = np.asarray(tokens)
    mask = np.asarray(mask) > 0
    d = tokens.shape[0] // n_chains
    out = np.zeros((n_chains, vocab_size))
    for m in range(n_chains):
        t = tokens[m * d:(m + 1) * d][mask[m * d:(m + 1) * d]]
        out[m] = np.bincount(t, minlength=vocab_size)
    return out


def load_limits(workload: str) -> dict:
    path = os.path.join(HERE, "limits", f"{workload}.json")
    with open(path) as f:
        return json.load(f)


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Every number at or under its limit.  A number that is missing,
    not finite or without a limit fails."""
    out, ok = {}, True
    for name, value in numbers.items():
        lim = limits.get(name, {}).get("limit")
        good = (lim is not None and value is not None
                and np.isfinite(value) and value <= lim)
        ok = ok and good
        out[name] = {"value": value, "limit": lim}
    for name in limits:
        if name not in numbers and not name.startswith("_"):
            ok = False
            out[name] = {"value": None, "limit": limits[name]["limit"]}
    return ok, out
