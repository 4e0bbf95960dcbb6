"""The reader of ``transfer_mb_per_batch``
(``bench/metrics/transfer_mb_per_batch.py``): bytes moved between host and
device per traced batch, and no reading from an untraced run, from a
program without the recorder, or from one that never counted bytes."""
import sys

import pytest

import benchutil as U  # noqa: F401  (puts src/ and the root on the path)
from bench import harness
from repro.core import telemetry

MS = 1_000_000
SPANS = [("sched.submit_window", 0, 10 * MS, -1, 0),
         ("sched.submit_window", 20 * MS, 30 * MS, -1, 1)]


def _reader(cell="usb-paper.b1"):
    return harness.per_layer_readers(
        U.BENCH, cell)["transfer_mb_per_batch"][1]


def _view(traced=True):
    return harness.RunView(harness.Spans(False), [], 1.0, [],
                           trace={"devices": 0} if traced else None)


@pytest.mark.parametrize("counters, ever, want", [
    ({"h2d_bytes": 3_000_000, "d2h_bytes": 1_000_000}, 1, 2.0),
    ({"d2h_bytes": 5_000_000}, 1, 2.5),
    ({"h2d": 12, "d2h": 12}, 0, None),   # a program that counts no bytes
])
def test_reads_megabytes_per_batch(monkeypatch, counters, ever, want):
    monkeypatch.setattr(telemetry, "snapshot", lambda: {
        "spans": SPANS, "counters": counters})
    monkeypatch.setitem(telemetry._COUNTS, "h2d_bytes", ever)
    monkeypatch.setitem(telemetry._COUNTS, "d2h_bytes", ever)
    assert _reader().read(_view()) == want


def test_every_cell_reads_it():
    for cell in (w["name"] for w in U.BENCH["workloads"]):
        assert "transfer_mb_per_batch" in harness.per_layer_readers(
            U.BENCH, cell)


def test_reads_nothing_untraced_or_without_the_recorder(monkeypatch):
    import repro.core
    reader = _reader()
    monkeypatch.setattr(telemetry, "snapshot", lambda: {
        "spans": SPANS, "counters": {"h2d_bytes": 8}})
    monkeypatch.setitem(telemetry._COUNTS, "h2d_bytes", 8)
    assert reader.read(_view(traced=False)) is None
    monkeypatch.delattr(repro.core, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    assert reader.read(_view()) is None
