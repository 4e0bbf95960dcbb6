"""Scheduler (``serving/online.py``, ``serving/scheduler.py``): wall per
timed batch inside ``submit_window`` but outside the solve and the drain —
staging, the ledger commit and the bookkeeping, ms."""
import numpy as np


def read(run):
    decide = run.per_window("decide")
    if not decide:
        return None
    parts = [run.per_window(n) for n in ("solve", "drain")]
    return float(np.mean([decide[w] - sum(p[w] for p in parts)
                          for w in decide])) * 1e3
