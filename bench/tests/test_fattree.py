"""CPU rehearsal of the ``fattree8-paper.b32-refdrain`` cell: the k=8
fat-tree (V=208) on the served path at two jobs a window.  The
configuration's pin holds, a run is ``correct``, the bfloat16 solver
control is not, and a traced run reads ``transfer_mb_per_batch``."""
import pytest

import benchutil as U
from bench import traffic as T

CELL = "fattree8-paper.b32-refdrain"


@pytest.fixture(scope="module")
def controlled():
    return U.run(CELL, U.small_spec(CELL, per_epoch=2), controls=True)


def test_pin_matches():
    spec = T.load_workload(CELL)
    dep = T.load_deployment(spec["config_file"])
    pin = spec["config_file"]["pin"]
    assert T.pin_of(dep.scenario) == pin
    assert pin["num_nodes"] == 208 and pin["max_layers"] == 34
    paper = T.load_workload("usb-paper.b32-refdrain")["config_file"]["pin"]
    assert pin["mix"] == paper["mix"]
    assert float(dep.scenario.mean_service_s) == dep.mean_service_s


def test_runs_correct_on_cpu(controlled):
    res = controlled["result"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == controlled["extra"]["placed"] > 0
    assert controlled["extra"]["sample"] > 0


def test_bfloat16_solver_control_is_not_correct(controlled):
    ctl = controlled["extra"]["controls"]
    assert any(ctl[k]["value"] > ctl[k]["limit"]
               for k in ("plan_gap", "bound_gap"))


def test_traced_run_reads_transfer_bytes():
    out = U.run(CELL, U.small_spec(CELL, per_epoch=2), trace=True)
    assert out["result"]["correct"] is True
    metrics = out["result"]["metrics"]
    assert metrics["transfer_mb_per_batch"]["value"] > 0.46
    assert metrics["transfer_mb_per_batch"]["unit"] == "MB/batch"
    assert metrics["host_syncs_per_batch"]["value"] == 1.0
