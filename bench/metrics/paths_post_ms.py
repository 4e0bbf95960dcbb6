"""Fused solver (``core/greedy.py``): wall of the program's span
``greedy.paths`` per traced batch: formatting the layer paths on the
host from the hops the fused solve fetched with its results, ms."""
from bench.metrics import _program as P


def read(run):
    return P.span_ms(run, "greedy.paths")
