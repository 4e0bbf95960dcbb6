"""Exact drain (``core/completions.py``, ``core/eventsim.py``): wall in
``OnlineScheduler.advance_to`` per timed batch, ms."""
import numpy as np


def read(run):
    per = run.per_window("drain")
    return float(np.mean(list(per.values()))) * 1e3 if per else None
