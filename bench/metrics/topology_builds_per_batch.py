"""Scheduler: builds of the health-scaled effective topology (counter
``topology_builds``) per traced batch, counted by the program; a build
follows a health event, so a healthy window reads 0.  A program that
never counted a build (one without the cached topology) reads nothing."""
from bench.metrics import _program as P


def read(run):
    got = P.counted(run, "topology_builds")
    if got is None:
        return None
    from repro.core import telemetry
    if not telemetry.counter("topology_builds"):
        return None
    return got[0] / got[1]
