"""Scheduler: solver calls inside the timed window that compiled a new
program (``plan.meta["jit_compiled"]``); there should be none."""


def read(run):
    timed = set(run.timed)
    return sum(1 for w, compiled in run.solves if compiled and w in timed)
