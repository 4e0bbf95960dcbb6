"""The check fails what it must, on the CPU at a size a test run holds:
the lower-precision controls, and the timed path broken underneath in
each way a one-chip cell of this scheduler can break."""
import dataclasses

import numpy as np
import pytest

import benchutil as U
from bench import check
from repro.core import greedy
from repro.serving.online import OnlineScheduler

CELL = "usb-paper.b32-refdrain"


def small():
    return U.small_spec(CELL, per_epoch=4)


def checks_of(out):
    return {k: v["value"] for k, v in out["result"]["checks"].items()}


def test_sound_run_and_controls():
    out = U.run(CELL, small(), controls=True)
    assert out["result"]["correct"] is True
    limits = small()["limits"]
    ok, _ = check.judge({k: v["value"] for k, v in
                         out["extra"]["controls"].items() if k in limits},
                        limits)
    assert not ok
    ctl = out["extra"]["controls"]
    assert ctl["bound_gap"]["value"] > ctl["bound_gap"]["limit"]
    assert ctl["drain_gap"]["value"] > ctl["drain_gap"]["limit"]


def test_state_returned_unchanged(monkeypatch):
    def frozen(self, t):
        self._now = max(self._now, float(t))     # the clock moves,
        self._stamp_clock()                      # nothing drains
    monkeypatch.setattr(OnlineScheduler, "advance_to", frozen)
    out = U.run(CELL, small())
    assert out["result"]["correct"] is False
    assert checks_of(out)["drain_gap"] == np.inf


def test_half_the_batch_left_out(monkeypatch):
    submit = OnlineScheduler.submit_window

    def half(self, t, infer_jobs, *, arrivals=None, **kw):
        k = (len(infer_jobs) + 1) // 2
        return submit(self, t, list(infer_jobs)[:k],
                      arrivals=None if arrivals is None else arrivals[:k],
                      **kw)
    monkeypatch.setattr(OnlineScheduler, "submit_window", half)
    out = U.run(CELL, small())
    assert out["result"]["correct"] is False
    assert checks_of(out)["unaccounted"] > 0


@pytest.mark.parametrize("what", ["bound", "route"])
def test_answer_altered_where_produced(monkeypatch, what):
    route = greedy.greedy_route

    def altered(net, batch, **kw):
        plan = route(net, batch, **kw)
        j = int(plan.order[0])
        if what == "bound":
            bounds = np.array(plan.bounds, np.float64)
            bounds[j] *= 1.01
            return dataclasses.replace(plan, bounds=bounds)
        assign = np.array(plan.assign)
        assign[j, 0] = (assign[j, 0] + 1) % net.num_nodes
        return dataclasses.replace(plan, assign=assign)
    monkeypatch.setattr(greedy, "greedy_route", altered)
    out = U.run(CELL, small())
    assert out["result"]["correct"] is False
    name = "bound_gap" if what == "bound" else "plan_gap"
    assert checks_of(out)[name] > small()["limits"][name]

