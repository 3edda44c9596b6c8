"""The control of the correctness check, at a cell's own size.

  python3 bench/control.py --workload <cell> --seeds 1,2,3

For each seed it builds the cell's traces as a run does, puts the plain
reference computed in 2 GB slices (one below the configuration's 1 GB)
in the program's place, and prints the check's numbers: a sound check
reads at least one of them above its limit.  The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    import run
    from cell import Cell, load_json
    _, wl = run.find_workload(args.workload)
    config = load_json("configs", wl["config"])
    traffic = load_json("traffic", wl["traffic"])
    kind = importlib.import_module(f"answers.{traffic['answer']}")
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        cell = Cell(config, traffic, seed)
        cell.make_traces()
        checks = kind.check(cell, kind.control(cell))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_fails": any(v > lim for v, lim
                                               in checks.values()),
                          "checks": checks,
                          "seconds": time.perf_counter() - t}), flush=True)


if __name__ == "__main__":
    main()
