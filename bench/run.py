#!/usr/bin/env python3
"""The benchmark's command.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` in one process on the chips the cell
asks for, and prints the result as the last line of standard output: one
JSON object with ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, ``checks`` last (each number the check compared, beside its
limit), and with ``--trace 1`` a ``breakdown``.  The same numbers are the
last lines of standard error.  It exits non-zero and prints no result
when JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr)
    raise SystemExit(1)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        fail(f"unknown workload {args.workload!r}; cells: {sorted(cells)}")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    devices = harness.open_devices(int(cells[args.workload]["chips"]))
    out = harness.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START, bench=bench,
                           devices=devices)
    res, extra = out["result"], out["extra"]
    print(harness.dumps({"extra": extra}), file=sys.stderr)
    for name, c in res["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(harness.dumps(res), flush=True)


if __name__ == "__main__":
    main()
