"""Scheduler: device-to-host transfers (``telemetry.to_host`` calls, each
one wait for the device) per traced batch, counted by the program."""
from bench.metrics import _program as P


def read(run):
    got = P.counted(run, "d2h")
    return None if got is None else got[0] / got[1]
