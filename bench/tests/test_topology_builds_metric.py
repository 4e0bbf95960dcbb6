"""The reader of ``topology_builds_per_batch``
(``bench/metrics/topology_builds_per_batch.py``): builds of the scheduler's
effective topology per traced batch, and no reading from an untraced run,
from a program without the recorder, or from one that never counts a
build."""
import sys

import pytest

import benchutil as U  # noqa: F401  (puts src/ and the root on the path)
from bench import harness
from repro.core import telemetry

MS = 1_000_000
SPANS = [("sched.submit_window", 0, 10 * MS, -1, 0),
         ("sched.topology", 0, 1 * MS, 0, 0),
         ("sched.submit_window", 20 * MS, 30 * MS, -1, 1)]


def _reader():
    return harness.per_layer_readers(
        U.BENCH, "usb-paper.b1")["topology_builds_per_batch"][1]


def _view(traced=True):
    return harness.RunView(harness.Spans(False), [], 1.0, [],
                           trace={"devices": 0} if traced else None)


@pytest.mark.parametrize("in_window, ever, want", [
    (1, 2, 0.5),    # one build in a window of two batches
    (0, 1, 0.0),    # a healthy window after the warm-up's build
    (0, 0, None),   # a program that builds without counting
])
def test_reads_builds_per_batch(monkeypatch, in_window, ever, want):
    monkeypatch.setattr(telemetry, "snapshot", lambda: {
        "spans": SPANS,
        "counters": {"d2h": 9, "topology_builds": in_window}})
    monkeypatch.setitem(telemetry._COUNTS, "topology_builds", ever)
    assert _reader().read(_view()) == want


def test_reads_nothing_untraced_or_without_the_recorder(monkeypatch):
    import repro.core
    reader = _reader()
    monkeypatch.setattr(telemetry, "snapshot", lambda: {
        "spans": SPANS, "counters": {"topology_builds": 1}})
    monkeypatch.setitem(telemetry._COUNTS, "topology_builds", 1)
    assert reader.read(_view(traced=False)) is None
    monkeypatch.delattr(repro.core, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    assert reader.read(_view()) is None
