"""Scheduler (``serving/scheduler.py``): wall of the program's span
``sched.commit`` per traced batch — the exact ledger's commit, the drain
engine's warm-up and the queue sync, ms."""
from bench.metrics import _program as P


def read(run):
    return P.span_ms(run, "sched.commit")
