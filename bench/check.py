"""The comparison that decides ``correct``.

It runs after the timed window, on what the window produced, against the
plain reference (:mod:`reference`), and returns one number per layer:

* ``plan_gap`` — fused solver.  For a sample of the window's batches, drawn
  from the seed, every round is replayed in the reference: under the
  queues the reference's own drain gives at the batch's commit instant,
  the job the program committed in that round, on its route and hops, is
  priced and set against the least bound of any job still left.  The
  number is the widest relative excess.  A route whose hops do not connect
  reads ``inf``, and so does a batch whose plan does not place each of its
  jobs once.
* ``bound_gap`` — fused solver.  The widest relative gap between the bound
  the program reported for a job and that job's price on its own route.
* ``drain_gap`` — exact drain.  Every committed plan (set-up's and the
  window's) is served in the reference, in priority order; the number is
  the widest gap between a completion the program recorded and the
  reference's, relative to the job's time in the system.  A job that the
  reference finishes before the program's clock and the program never
  recorded reads ``inf``.
* ``unaccounted`` — stream pipeline.  Requests of the window that were not
  placed exactly once or shed with a reason.

``controls`` swaps lower-precision references in for the program's
answers: the solver in bfloat16 (the program states float32), the drain
in float32 (the program states float64).  Those have to come out not
correct.
"""
from __future__ import annotations

import collections
import dataclasses

import numpy as np

from bench import reference as R


@dataclasses.dataclass
class Placed:
    """One job of a committed plan, in priority order."""

    name: str
    assign: list
    bound: float
    paths: list


@dataclasses.dataclass
class Window:
    """One batch handed to the scheduler."""

    t: float
    names: list                     # window order
    timed: bool
    placed: list                    # [Placed], priority order


def _jobs(requests: dict, dep) -> dict:
    out = {}
    for name, q in requests.items():
        comp, data = dep.profiles[q.kind]
        out[name] = R.Job(name, q.src, q.dst,
                          np.asarray(comp, np.float64),
                          np.asarray(data, np.float64))
    return out


def _plan_numbers(net, qn, ql, jobs, names, placed) -> tuple[float, float]:
    if sorted(p.name for p in placed) != sorted(names):
        return np.inf, np.inf
    qn, ql = qn.copy(), ql.copy()
    left = list(names)
    plan_gap = bound_gap = 0.0
    for p in placed:
        best = min(R.optimal_costs(net, qn, ql, [jobs[n] for n in left]))
        cost = R.route_cost(net, qn, ql, jobs[p.name], p.assign, p.paths)
        if not np.isfinite(cost):
            return np.inf, np.inf
        plan_gap = max(plan_gap, abs(cost - best) / best)
        bound_gap = max(bound_gap, abs(float(p.bound) - cost) / cost)
        R.commit(qn, ql, jobs[p.name], p.assign, p.paths)
        left.remove(p.name)
    return plan_gap, bound_gap


def _control_plan(dep, qn, ql, jobs, names) -> list:
    net = R.Net(dep.mu_node, dep.mu_link, R.Prec("bfloat16"))
    rounds = R.greedy(net, qn, ql, [jobs[n] for n in names])
    return [Placed(names[i], assign, bound, paths)
            for i, bound, assign, paths in rounds]


def compare(dep, requests: dict, windows: list[Window], completions: dict,
            clock: float, *, sample: list[int],
            controls: bool = False) -> dict:
    """Numbers of one run (see the module docstring).  With ``controls``
    the lower-precision references stand in for the program's answers."""
    jobs = _jobs(requests, dep)
    net = R.Net(dep.mu_node, dep.mu_link)
    V = net.V
    tl = R.Timelines(dep.mu_node, dep.mu_link)
    tl32 = (R.Timelines(dep.mu_node, dep.mu_link, R.Prec("float32"))
            if controls else None)
    committed: dict = collections.defaultdict(float)
    ref_done: dict = {}
    ctl_done: dict = {}
    release: dict = {}
    out = {"plan_gap": 0.0, "bound_gap": 0.0}
    want = set(sample)
    for k, w in enumerate(windows):
        if k in want and w.names:
            qn, ql = R.residual_queues(tl, committed, w.t, V)
            placed = (_control_plan(dep, qn, ql, jobs, w.names)
                      if controls else w.placed)
            pg, bg = _plan_numbers(net, qn, ql, jobs, w.names, placed)
            out["plan_gap"] = max(out["plan_gap"], pg)
            out["bound_gap"] = max(out["bound_gap"], bg)
        for p in w.placed:
            st = R.stages(jobs[p.name], p.assign, p.paths)
            ref_done[p.name] = tl.run(st, w.t)
            if tl32 is not None:
                ctl_done[p.name] = tl32.run(st, w.t)
            release[p.name] = w.t
            for key, work in st:
                committed[key] += work
    got = ({n: c for n, c in ctl_done.items() if c <= clock}
           if controls else completions)
    eps = 1e-9 * max(1.0, abs(clock))
    gap = 0.0
    for name, ref in ref_done.items():
        if name not in got:
            if ref < clock - eps:
                gap = np.inf
            continue
        gap = max(gap, abs(got[name] - ref)
                  / max(ref - release[name], 1e-30))
    if set(got) - set(ref_done):
        gap = np.inf
    out["drain_gap"] = gap
    return out


def accounting(requests: dict, windows: list[Window], shed: list) -> dict:
    """Requests of the timed window: placed, shed with a reason, and not
    accounted for."""
    timed = {n for n, q in requests.items() if q.timed}
    placed = collections.Counter(p.name for w in windows if w.timed
                                 for p in w.placed)
    failed = {s["name"] for s in shed if s["name"] in timed}
    ok = {n for n in timed if placed.get(n, 0) == 1} | failed
    bad = len(timed - ok) + sum(c - 1 for c in placed.values() if c > 1) \
        + len(set(placed) - timed)
    return {"attempted": len(timed),
            "placed": sum(1 for n in timed if placed.get(n, 0) == 1),
            "failed": len(failed),
            "unaccounted": bad}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number beside its limit; correct when none exceeds it."""
    checks = {k: {"value": float(v), "limit": float(limits[k])}
              for k, v in numbers.items()}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
