"""Shared by the readers of the program's own recorder
(``repro.core.telemetry``).  The recorder follows the profiler: in a
traced run it holds the spans and counters of the windows committed while
the trace was on, one ``sched.submit_window`` span per batch.  A program
without the recorder, or an untraced run, gives nothing to read."""


def recorded(run):
    """``(snapshot, batches)``, or ``None`` where there is nothing."""
    if run.trace is None:
        return None
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    snap = telemetry.snapshot()
    n = sum(1 for s in snap["spans"] if s[0] == "sched.submit_window")
    return (snap, n) if n else None


def span_ms(run, name: str):
    """Summed wall of the spans ``name`` per batch, ms."""
    got = recorded(run)
    if got is None:
        return None
    snap, n = got
    return sum(t1 - t0 for s, t0, t1, *_ in snap["spans"] if s == name) \
        / n * 1e-6


def counted(run, name: str):
    """Counter ``name`` while the recorder was on, and the batches."""
    got = recorded(run)
    if got is None:
        return None
    snap, n = got
    return snap["counters"].get(name, 0), n
