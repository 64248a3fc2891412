"""Every cell of BENCHMARK.json names a configuration, a traffic, limits
and metrics that exist as files, and the file keeps the contract's
shape."""
import importlib
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH_FIELDS = {"use_pallas", "pred_doc_block", "train_doc_block",
               "sweeps_per_launch", "length_buckets", "bucket_token_block",
               "bucket_overhead_docs", "sampler_mode", "sparse_topic_cap",
               "count_rebuild_every", "product_form_sweeps",
               "fuse_weighted_predict", "chains_per_device"}
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_exist(cell):
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    conf = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    assert os.path.isfile(os.path.join(ROOT, conf["file"]))
    traffic = json.load(open(os.path.join(
        ROOT, "bench", "traffic", f"{w['traffic']}.json")))
    importlib.import_module(f"bench.drivers.{traffic['driver']}")
    limits = json.load(open(os.path.join(
        ROOT, "bench", "limits", f"{cell}.json")))
    assert limits and all("limit" in v for k, v in limits.items()
                          if not k.startswith("_"))
    assert w["chips"] in (1, 4)


def test_every_metric_has_a_reader_and_a_clean_name():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           f"{m['name']}.py")), m["name"]
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


def test_each_cell_reports_setup_another_end_to_end_and_a_layer():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for cell in CELLS:
        mine = [n for n, m in e2e.items() if cell in m.get("workloads", [cell])]
        assert "setup_s" in mine and len(mine) >= 2
        layers = [m for m in BENCH["per_layer"]
                  if cell in m.get("workloads", CELLS)]
        assert layers
        for m in layers:       # the end-to-end metric it moves is reported
            assert m["moves"] in mine


def test_configs_hold_no_path_choosing_field():
    for c in BENCH["configs"]:
        conf = json.load(open(os.path.join(ROOT, c["file"])))
        assert not PATH_FIELDS & set(conf), c["name"]
        assert conf["reduced"] == c["reduced"]


def test_bounds_within_the_contract():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
