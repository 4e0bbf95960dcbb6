"""The per-layer readers of the program's own recorder
(``bench/metrics/_program.py``): what they compute from a snapshot, that
they read nothing from a program without the recorder or from an untraced
run, and that program spans in a profile leave the trace reduction's
numbers as they were."""
import sys

import jax
import pytest

import benchutil as U  # noqa: F401  (puts src/ and the root on the path)
from bench import harness, trace
from repro.core import telemetry

READERS = ("solve_stage_ms", "solve_fetch_ms", "paths_post_ms",
           "ledger_commit_ms", "host_syncs_per_batch",
           "jit_misses_in_window")


def _readers():
    return {n: harness.per_layer_readers(U.BENCH, "usb-paper.b1")[n][1]
            for n in READERS}


def _view(traced=True):
    return harness.RunView(harness.Spans(False), [], 1.0, [],
                           trace={"devices": 0} if traced else None)


MS = 1_000_000
SPANS = [("sched.submit_window", 0, 10 * MS, -1, 0),
         ("greedy.stage", 0, 3 * MS, 0, 0),
         ("sched.submit_window", 20 * MS, 30 * MS, -1, 1),
         ("greedy.stage", 20 * MS, 21 * MS, 2, 1),
         ("greedy.fetch", 21 * MS, 25 * MS, 2, 1),
         ("sched.commit", 25 * MS, 26 * MS, 2, 1)]


def test_readers_compute_per_batch(monkeypatch):
    monkeypatch.setattr(telemetry, "snapshot", lambda: {
        "spans": SPANS, "counters": {"d2h": 9, "h2d": 11}})
    got = {n: m.read(_view()) for n, m in _readers().items()}
    assert got == {"solve_stage_ms": 2.0, "solve_fetch_ms": 2.0,
                   "paths_post_ms": 0.0, "ledger_commit_ms": 0.5,
                   "host_syncs_per_batch": 4.5, "jit_misses_in_window": 0}
    assert all(m.read(_view(traced=False)) is None
               for m in _readers().values())


def test_readers_read_nothing_without_the_recorder(monkeypatch):
    """A program without ``repro.core.telemetry`` (an older commit) gives
    no reading, and no error."""
    import repro.core
    readers = _readers()
    monkeypatch.setattr(telemetry, "snapshot", lambda: {
        "spans": SPANS, "counters": {}})
    assert readers["solve_stage_ms"].read(_view()) == 2.0
    monkeypatch.delattr(repro.core, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    assert all(m.read(_view()) is None for m in readers.values())


def _profile(path, program_spans: bool):
    @jax.jit
    def probe(x):
        return x @ x

    x = jax.numpy.ones((32, 32))
    probe(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.decide"):
                with jax.profiler.TraceAnnotation("bench.solve"):
                    if program_spans:
                        with jax.profiler.TraceAnnotation("repro.solve"):
                            with jax.profiler.TraceAnnotation(
                                    "repro.greedy.fetch"):
                                probe(x).block_until_ready()
                    else:
                        probe(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    return trace.reduce(path, programs=("probe",))


@pytest.mark.parametrize("reader", ["solve_device_ms", "device_idle_pct"])
def test_program_spans_leave_the_trace_reduction_as_it_was(tmp_path, reader):
    with_p = _profile(tmp_path / "with", True)
    without = _profile(tmp_path / "without", False)
    assert with_p["spans"] == without["spans"] == {"decide": 3, "solve": 3}
    assert with_p["devices"] == without["devices"]
    mod = harness.per_layer_readers(U.BENCH, "usb-paper.b1")[reader][1]
    views = []
    for red in (with_p, without):
        v = harness.RunView(harness.Spans(False), [0, 1, 2], 1.0, [],
                            trace=red, trace_window_s=1.0)
        views.append(mod.read(v))
    assert views[0] == views[1]
