"""Readings that the limits of bench/limits/<cell>.json are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --faults 3

In one process: the program on `--seeds` seeds, then the precision
control and every planted fault (bench/faults.py) on `--faults` seeds
each, every run through the same harness as bench/run.py with a short
window.  Prints one JSON line per run with the numbers compared; the
limits play no part.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import faults, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=7_000_000_001)
    ap.add_argument("--seconds", type=float, default=0.5)
    ap.add_argument("--only", default="",
                    help="comma-separated subset of program," +
                    ",".join(faults.KINDS + faults.READINGS))
    args = ap.parse_args(argv)
    spec = run.cell_spec(args.workload)
    kind = spec["traffic"].get("entry", spec["traffic"]["driver"])
    plan = [("program", i) for i in range(args.seeds)]
    plan += [(f, i) for f in faults.KINDS + faults.READINGS
             for i in range(args.faults)]
    only = set(filter(None, args.only.split(",")))
    for what, i in plan:
        if only and what not in only:
            continue
        seed = args.first_seed + 7919 * i
        patches = () if what == "program" else (faults.planted(what, kind),)
        try:
            res = run.run(args.workload, seed, args.seconds, False,
                          spec=spec, patches=patches)
            line = {"what": what, "seed": seed, "correct": res["correct"],
                    "attempted": res["attempted"], "failed": res["failed"],
                    "numbers": {k: v["value"]
                                for k, v in res["checks"].items()}}
        except Exception as e:  # a fault may crash the run: that fails it
            line = {"what": what, "seed": seed, "error": repr(e)[:300]}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
