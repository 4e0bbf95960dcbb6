"""Fused solver: bytes through the host<->device transfers (counters
``h2d_bytes`` and ``d2h_bytes``) per traced batch, MB.  A program that
never counted transfer bytes reads nothing."""
from bench.metrics import _program as P


def read(run):
    up, down = P.counted(run, "h2d_bytes"), P.counted(run, "d2h_bytes")
    if up is None:
        return None
    from repro.core import telemetry
    if not (telemetry.counter("h2d_bytes") or telemetry.counter("d2h_bytes")):
        return None
    return (up[0] + down[0]) / up[1] / 1e6
