"""Fused solver (``core/greedy.py``): wall of the program's span
``greedy.stage`` per traced batch — padding, the dedupe plan and its
transfer to the device, ms."""
from bench.metrics import _program as P


def read(run):
    return P.span_ms(run, "greedy.stage")
