"""Fused solver (``core/greedy.py``): median wall of one call of the
solver registry's ``greedy``, which ends in a host sync, ms."""
import numpy as np


def read(run):
    d = run.durations("solve")
    return float(np.median(d)) * 1e3 if d else None
