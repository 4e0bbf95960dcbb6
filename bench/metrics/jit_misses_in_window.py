"""Scheduler: programs lowered for a new signature while the traced part
of the window ran (jit cache misses of every program, eager ops
included), counted by the program; there should be none."""
from bench.metrics import _program as P


def read(run):
    got = P.counted(run, "jit_misses")
    return None if got is None else got[0]
