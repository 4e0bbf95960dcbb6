"""Fused solver (``core/greedy.py``): wall of the program's span
``greedy.paths`` per traced batch — the path post-pass: its operands'
round trip, the ``_walk_paths`` dispatch and its fetch, ms."""
from bench.metrics import _program as P


def read(run):
    return P.span_ms(run, "greedy.paths")
