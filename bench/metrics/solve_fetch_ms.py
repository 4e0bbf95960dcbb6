"""Fused solver (``core/greedy.py``): wall of the program's span
``greedy.fetch`` per traced batch — the wait for the fused scan's outputs
and their copy to the host, ms."""
from bench.metrics import _program as P


def read(run):
    return P.span_ms(run, "greedy.fetch")
