"""CPU rehearsal of the benchmark: one cell's harness driven for about a
second, the result's keys, and the names, units and readers of every
metric in BENCHMARK.json.  The command itself still refuses the CPU."""
import re
import subprocess
import sys

import pytest

import benchutil as U
from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_names_units_and_readers():
    b = U.BENCH
    cells = {w["name"] for w in b["workloads"]}
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in b["per_layer"]:
        mod = harness.per_layer_readers(b, next(iter(
            m.get("workloads", cells))))[m["name"]][1]
        assert callable(mod.read)
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}
    for x in b["workloads"] + b["configs"]:
        assert NAME.match(x["name"])
    for w in b["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert len(w["why"]) <= 200


@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_on_cpu(trace):
    out = U.run("usb-paper.b1", U.small_spec("usb-paper.b1"), trace=trace)
    res = out["result"]
    assert list(res)[:3] == ["correct", "attempted", "failed"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == out["extra"]["placed"] > 0
    want = ({m["name"] for m in U.BENCH["per_layer"]} - {
        "solve_device_ms", "device_idle_pct"} if trace
        else {m["name"] for m in U.BENCH["end_to_end"]})
    assert set(res["metrics"]) == want
    assert res["metrics"].get("compiles_in_window", {"value": 0})[
        "value"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])
    if trace:
        assert {"busy_s", "window_s"} <= set(res["device"])
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    line = harness.dumps(res)
    assert "NaN" not in line and "Infinity" not in line


def test_command_refuses_the_cpu():
    p = subprocess.run(
        [sys.executable, str(U.ROOT / "bench" / "run.py"), "--workload",
         "usb-paper.b1", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=U.ROOT, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0 and p.stdout.strip() == ""
