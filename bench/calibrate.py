#!/usr/bin/env python3
"""Readings for the check's limits, many seeds in one process.

  python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 4

For each seed it runs the cell once (fresh scheduler, same set-up, the
timed window at the cell's own load) and prints one JSON line: the
numbers the check compared, and under ``"controls"`` the same numbers
with the lower-precision references in the program's place.  The lower
reading of a limit is the largest program number over the seeds, the
upper reading the smallest control number (``PERF.md`` keeps both).  A
workload file need not be a cell of ``BENCHMARK.json``: the files of the
cells left out (``PERF.md``, Open questions) run here too.  Like
``run.py``, it fails without a TPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    devices = harness.open_devices(1)
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = harness.run_cell(args.workload, seed, args.seconds, False,
                               t_start=t0, bench=bench, devices=devices,
                               controls=True)
        res, extra = out["result"], out["extra"]
        print(harness.dumps({
            "seed": seed, "correct": res["correct"],
            "checks": {k: v["value"] for k, v in res["checks"].items()}
            | extra["not_compared"],
            "controls": {k: v["value"] for k, v in extra["controls"].items()},
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "per_layer": extra["per_layer"], "windows": extra["windows"],
            "run_s": time.perf_counter() - t0}), flush=True)


if __name__ == "__main__":
    main()
