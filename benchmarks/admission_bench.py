"""Deadline-aware admission & SLO-guarded auto re-planning, A/B'd.

  PYTHONPATH=src python benchmarks/admission_bench.py [--smoke] [--out PATH]

Two experiments, both driven by the exact-drain fork
(:func:`repro.core.completions.predict_completions`):

  * **Admission sweep** — per scenario x offered load, the identical
    arrival stream (every request carrying a relative deadline) is run
    under each admission policy: ``admit_all`` (baseline, no gating),
    ``reject`` (predicted misses shed on arrival) and ``defer``
    (predicted misses parked and re-assessed until they expire).
    ``BENCH_admission.json`` records SLO-miss rate, goodput (met
    deadlines) and shed counts per cell.  At every overload point
    (load >= ``OVERLOAD``) CI gates that *each* gated policy beats
    admit-all: strictly lower SLO-miss rate at equal-or-better goodput —
    the point of predictive admission is refusing work you'd have missed
    anyway, not refusing goodput.
  * **Re-planning under faults** — per fault family, the same faulted
    stream runs with re-planning off (``none``), with the hysteresis
    monitor (``auto``: threshold + cooldown + exponential backoff +
    budget), and eagerly (``eager``: threshold 0, no cooldown — the
    replan-on-every-observation strawman); a clairvoyant **oracle**
    (degraded topology known from t=0) anchors the latency scale.  CI
    gates that ``auto`` stays within its trigger budget and never
    re-plans more often than ``eager``.

Both experiments are only meaningful if the fork is honest, so the run
opens with a **prediction-exactness gate**: on every benchmarked
scenario, predictions taken at a queued mid-run state must match the
completions the live drain then realizes to ``EXACT_RTOL`` — if that
fails the whole benchmark exits non-zero before reporting numbers.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

_ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_ROOT))
sys.path.insert(0, str(_ROOT / "src"))

import numpy as np

POLICIES = ("admit_all", "reject", "defer")
OVERLOAD = 1.5               # loads >= this must show the admission win
EXACT_RTOL = 1e-9            # fork honesty bar (the tentpole invariant)
DEADLINE_FACTOR = 1.2        # SLO = factor x mean service time

SMOKE_CASES = [
    dict(name="paper-small", arrivals=20, loads=(1.75,), batch=2),
]
FULL_CASES = [
    dict(name="paper-small", arrivals=32, loads=(0.8, 1.75, 2.5), batch=2),
    dict(name="edge-cloud", arrivals=32, loads=(1.75,), batch=2),
]

SMOKE_FAMILIES = ("transient-node",)
FULL_FAMILIES = ("transient-node", "elastic", "cascade")
REPLAN_BUDGET = 4


# -- gate 0: the fork is honest ----------------------------------------------

def _prediction_gap(name: str, *, windows: int = 3, batch: int = 2) -> float:
    """Worst relative gap between a queued-state prediction and the
    realized completions on one scenario (the test_predict invariant,
    re-checked in situ on the benchmark's own catalog)."""
    from repro.core import completions as C
    from repro.scenarios import make_scenario
    from repro.serving.online import OnlineScheduler

    sc = make_scenario(name, seed=0)
    rng = np.random.default_rng(13)
    sched = OnlineScheduler(sc.topology, drain="exact")
    t = 0.0
    for _ in range(windows):
        sched.submit_window(t, sc.sample_jobs(rng, batch),
                          pad_to=sc.max_layers)
        t += 0.05
    preds = C.predict_completions(sched._effective_topology(), sched.ledger)
    realized = sched.finish()
    gap = 0.0
    for name_, t_done in realized.items():
        denom = max(abs(t_done), 1e-12)
        gap = max(gap, abs(preds[name_] - t_done) / denom)
    return gap


# -- experiment 1: admission sweep -------------------------------------------

def _admission_cell(sc, *, load: float, arrivals: int, batch: int,
                    policy: str, seed: int) -> dict:
    from repro.serving.online import run_online

    rate = sc.nominal_rate(load)
    tr = run_online(sc, horizon=arrivals / (rate * batch), seed=seed,
                    rate=rate * batch, batch_size=batch, drain="exact",
                    finish=True, admission=policy,
                    deadline_s=DEADLINE_FACTOR * sc.mean_service_s)
    s = tr.summary()
    slo = s["slo"]
    return {
        "policy": policy,
        "slo_miss_rate": slo.get("slo_miss_rate"),
        "goodput": slo["goodput"],
        "offered": slo["offered"],
        "met": slo["met"],
        "late": slo["late"],
        "shed_by_reason": s.get("shed_by_reason", {}),
        "admission": s.get("admission", {}),
    }


def _admission_case(case: dict, *, seed: int, verbose: bool) -> dict:
    from repro.scenarios import make_scenario

    sc = make_scenario(case["name"], seed=0)
    points = []
    for load in case["loads"]:
        cells = {p: _admission_cell(sc, load=load,
                                    arrivals=case["arrivals"],
                                    batch=case["batch"], policy=p,
                                    seed=seed) for p in POLICIES}
        base = cells["admit_all"]
        gated_wins = True
        if load >= OVERLOAD:
            for p in ("reject", "defer"):
                g = cells[p]
                gated_wins &= (g["slo_miss_rate"] < base["slo_miss_rate"]
                               and g["goodput"] >= base["goodput"])
        points.append({"load": load, "cells": cells,
                       "overload": load >= OVERLOAD,
                       "gated_beats_admit_all": gated_wins})
        if verbose:
            row = " ".join(
                f"{p}:miss={cells[p]['slo_miss_rate']:.2f}/"
                f"good={cells[p]['goodput']}" for p in POLICIES)
            print(f"  {case['name']:12s} load={load:<4} {row} "
                  f"win={gated_wins}", flush=True)
    return {"scenario": case["name"], "arrivals": case["arrivals"],
            "deadline_factor": DEADLINE_FACTOR, "points": points}


# -- experiment 2: auto-replan vs eager vs oracle under faults ----------------

def _replan_arm(sc, *, schedule, load: float, arrivals: int, seed: int,
                auto_replan) -> dict:
    from repro.serving.online import run_online

    rate = sc.nominal_rate(load)
    tr = run_online(sc, horizon=arrivals / rate, seed=seed, rate=rate,
                    batch_size=2, drain="exact", finish=True,
                    fault_schedule=schedule, auto_replan=auto_replan)
    s = tr.summary()
    act = tr.actual_latencies()
    return {
        "p50_actual_s": float(np.percentile(act, 50)) if act.size else None,
        "p99_actual_s": float(np.percentile(act, 99)) if act.size else None,
        "max_backlog_s": s["max_backlog_s"],
        "replans": s.get("replans", 0),
        "triggers": s.get("auto_replan_triggers", 0),
        "skipped": s.get("replans_skipped", {}),
    }


def _replan_case(family: str, *, seed: int, verbose: bool) -> dict:
    from repro.scenarios import make_scenario
    from repro.serving import faults as F
    from repro.serving.admission import ReplanPolicy

    sc = make_scenario("paper-small", seed=0)
    load, arrivals = 1.5, 20
    horizon = arrivals / sc.nominal_rate(load)
    schedule = F.make_fault_schedule(family, sc, horizon, seed=seed)
    auto_policy = ReplanPolicy(threshold=0.15, cooldown_s=horizon / 20,
                               backoff=2.0, budget=REPLAN_BUDGET,
                               min_improvement=0.02)
    eager_policy = ReplanPolicy(threshold=0.0, cooldown_s=0.0)

    arms = {
        "none": _replan_arm(sc, schedule=schedule, load=load,
                            arrivals=arrivals, seed=seed, auto_replan=None),
        "auto": _replan_arm(sc, schedule=schedule, load=load,
                            arrivals=arrivals, seed=seed,
                            auto_replan=auto_policy),
        "eager": _replan_arm(sc, schedule=schedule, load=load,
                             arrivals=arrivals, seed=seed,
                             auto_replan=eager_policy),
    }
    # Clairvoyant anchor: the first failed resource is down from t=0 (no
    # disruption ever) — only meaningful for families that fail something.
    fails = [e for e in schedule if e.kind in ("node_fail", "link_fail")]
    if fails:
        first = fails[0]
        ev = (F.node_fail(0.0, first.node) if first.kind == "node_fail"
              else F.FaultEvent(0.0, "link_fail", link=first.link))
        arms["oracle"] = _replan_arm(sc, schedule=F.FaultSchedule((ev,)),
                                     load=load, arrivals=arrivals,
                                     seed=seed, auto_replan=None)
    bounded = arms["auto"]["triggers"] <= REPLAN_BUDGET
    no_thrash = arms["auto"]["triggers"] <= max(arms["eager"]["triggers"],
                                                REPLAN_BUDGET)
    if verbose:
        row = " ".join(f"{k}:p99={v['p99_actual_s']:.2f}s/"
                       f"replans={v['replans']}" for k, v in arms.items())
        print(f"  {family:16s} {row} bounded={bounded}", flush=True)
    return {"family": family, "load": load, "arrivals": arrivals,
            "budget": REPLAN_BUDGET,
            "fault_events": [(e.time, e.kind, e.node) for e in schedule],
            "arms": arms, "auto_bounded": bounded,
            "auto_no_thrash": no_thrash}


def run(*, smoke: bool = False, seed: int = 7, verbose: bool = True) -> dict:
    cases = SMOKE_CASES if smoke else FULL_CASES
    families = SMOKE_FAMILIES if smoke else FULL_FAMILIES

    gaps = {c["name"]: _prediction_gap(c["name"]) for c in cases}
    exact = all(g <= EXACT_RTOL for g in gaps.values())
    if verbose:
        print(f"prediction exactness: worst={max(gaps.values()):.2e} "
              f"(rtol {EXACT_RTOL:g}) ok={exact}", flush=True)

    admission = [_admission_case(c, seed=seed, verbose=verbose)
                 for c in cases]
    replan = [_replan_case(f, seed=seed, verbose=verbose) for f in families]

    out = {
        "benchmark": "admission",
        "smoke": smoke,
        "exactness_rtol": EXACT_RTOL,
        "prediction_gaps": gaps,
        "prediction_exact": exact,
        "overload_threshold": OVERLOAD,
        "admission": admission,
        "replan": replan,
        "all_overload_wins": all(
            p["gated_beats_admit_all"]
            for c in admission for p in c["points"] if p["overload"]),
        "all_replan_bounded": all(r["auto_bounded"] and r["auto_no_thrash"]
                                  for r in replan),
    }
    if verbose:
        print(f"prediction_exact={out['prediction_exact']} "
              f"all_overload_wins={out['all_overload_wins']} "
              f"all_replan_bounded={out['all_replan_bounded']}", flush=True)
    return out


def main() -> None:
    from repro.launch import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="1 scenario, 1 overload point, 1 fault family "
                         "(the CI gate: exact fork + admission win + "
                         "bounded replans)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", default=str(pathlib.Path(__file__).parent
                                         / "BENCH_admission.json"))
    args = ap.parse_args()
    record = run(smoke=args.smoke, seed=args.seed)
    pathlib.Path(args.out).write_text(json.dumps(record, indent=2))
    print(f"wrote {args.out}")
    if not record["prediction_exact"]:
        raise SystemExit("what-if fork diverged from the realized drain — "
                         "admission numbers would be meaningless")
    if not record["all_overload_wins"]:
        raise SystemExit("a gated admission policy failed to beat admit-all "
                         "under overload (lower miss at >= goodput)")
    if not record["all_replan_bounded"]:
        raise SystemExit("auto re-planning exceeded its trigger budget or "
                         "out-replanned the eager strawman")


if __name__ == "__main__":
    main()
