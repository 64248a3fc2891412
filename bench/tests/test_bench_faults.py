"""A run with the timed path broken underneath comes out not correct.

Each case drives the whole of a run but the look for a chip (set-up,
window, check, the cell's own limits) at a size a test run can hold, on
the CPU: the program as it is must come out correct, and each fault of
bench/faults.py, and the bfloat16 control, must not."""
import pytest

from bench import faults, run

TINY = dict(n_topics=8, vocab_size=200, n_docs=320, n_train=240, max_len=24,
            n_iters=10, n_chains=4, n_pred_burnin=4, n_pred_samples=3,
            length={"dist": "lognormal", "median": 8.0, "sigma": 0.75,
                    "min": 4})
CELLS = {"mdna.weighted": "weighted", "imdb.train": "train_chains",
         "mdna.serve": "serve"}
SEED = 2 ** 33 + 12345


def tiny_run(cell, patches=()):
    spec = run.cell_spec(cell)
    spec["conf"].update(TINY)
    if spec["traffic"]["driver"] == "serve":
        spec["traffic"]["rate_per_s"] = 100.0
    return run.run(cell, SEED, 0.2, False, need_chip=False, cache=False,
                   spec=spec, patches=patches)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_program_is_correct(cell):
    res = tiny_run(cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


@pytest.mark.parametrize("fault", faults.KINDS)
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_fault_is_not_correct(cell, fault):
    res = tiny_run(cell, (faults.planted(fault, CELLS[cell]),))
    assert not res["correct"], res["checks"]
