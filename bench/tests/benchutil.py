"""Shared by the benchmark's tests: a cell's harness driven on the CPU at
a size a test run holds."""
import copy
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402

from bench import harness, traffic as T  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def small_spec(cell: str, *, per_epoch: int | None = None,
               warmup_windows: int = 3, **traffic) -> dict:
    spec = copy.deepcopy(T.load_workload(cell))
    if per_epoch is not None:
        spec["traffic"]["per_epoch"] = per_epoch
        spec["traffic"]["max_batch"] = per_epoch
    spec["traffic"].update(traffic)
    spec["warmup_windows"] = warmup_windows
    spec["check"]["sample_windows"] = 4
    return spec


def run(cell: str, spec: dict, *, seed: int = 2**31 + 7,
        seconds: float = 1.0, trace: bool = False,
        controls: bool = False) -> dict:
    return harness.run_cell(cell, seed, seconds, trace,
                            t_start=time.perf_counter(), bench=BENCH,
                            devices=jax.devices(), controls=controls,
                            spec=spec)
