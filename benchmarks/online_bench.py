"""Online serving benchmark: latency/backlog vs offered load, drain vs
no-drain, and the fluid-vs-exact drain fidelity gap.

  PYTHONPATH=src python benchmarks/online_bench.py [--smoke] [--out PATH]

For each scenario x offered-load factor, drives a sub-capacity Poisson
arrival stream through the online loop twice — with queue draining (the
time-aware scheduler) and without (the legacy commit-only loop) — and
records p50/p99 latency bounds and the backlog trajectory.  The headline
flags in ``BENCH_online.json``:

  * ``drain_bounded``    — the draining run's peak backlog is flat over the
                           run's second half (growth <= 1.3x),
  * ``nodrain_diverges`` — the no-drain run keeps climbing (>= 1.5x),
  * ``static_bounds_match`` — the static greedy path still reproduces the
                           pre-split quickstart bounds bit-for-bit.

The **fidelity** section measures how honest each drain model's numbers
are, per arrival, against the event simulator's ground truth:

  * the same plans the fluid run committed are replayed under exact
    (committed-work) accounting — the backlog gap is the fluid model's
    optimism, with policy decisions held fixed;
  * each run's claimed latency bounds are compared with the actual
    completion times of a full-horizon event replay: fluid bounds can be
    *violated* (it under-counts residual work); exact-drain bounds must
    dominate actuals (``all_exact_bounds_hold``);
  * the exact run's incrementally recorded completions must equal the
    one-shot replay (``all_exact_match_replay``) — the chunked drain is
    event-exact, not an approximation;
  * ``fluid_matches_seed`` — the default fluid trajectory is bit-identical
    to the pre-ledger capture (the exact drain is strictly opt-in).

``--smoke`` (2 scenarios, short streams, fidelity on paper-small) is the
CI regression gate: it fails on ``fluid_matches_seed``,
``all_exact_bounds_hold``, or ``all_exact_match_replay`` regressions.
Full mode includes the ``us-backbone:lm`` scale sweep (24-node USNET,
LM-profile traffic) in both the load sweep and the fidelity section — the
exact drain there runs on the indexed event engine
(:mod:`repro.core.eventsim`); ``benchmarks/drain_bench.py`` measures that
engine's throughput against the reference loop.
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

SMOKE_SCENARIOS = ["star", "edge-cloud:synthetic"]
FULL_SCENARIOS = ["star", "random-geometric", "edge-cloud:synthetic",
                  "paper-small", "us-backbone:lm"]
FIDELITY_SMOKE_SCENARIOS = ["paper-small"]
FIDELITY_FULL_SCENARIOS = ["paper-small", "star", "edge-cloud:synthetic",
                           "random-geometric", "us-backbone:lm"]

DRAIN_BOUNDED_MAX_GROWTH = 1.3
NODRAIN_MIN_GROWTH = 1.5
FIDELITY_LOAD = 0.9          # high enough that the optimism gap shows
BOUND_TOL = 1e-6             # relative slack for bound >= actual checks


def _static_bounds_match() -> bool:
    """Quickstart greedy bounds, bit-compared against the pre-split record."""
    from repro.core import solve
    from benchmarks.common import (QUICKSTART_BOUNDS, QUICKSTART_ORDER,
                                   quickstart_instance)

    net, batch = quickstart_instance()
    plan = solve(net, batch, method="greedy")
    return (plan.bounds.tolist() == QUICKSTART_BOUNDS
            and plan.order.tolist() == QUICKSTART_ORDER)


def run(*, smoke: bool = False, arrivals: int = 80, seed: int = 1,
        loads: tuple[float, ...] = (0.3, 0.6, 0.9),
        verbose: bool = True) -> list[dict]:
    from repro.scenarios import make_scenario
    from repro.serving.online import run_online

    scenarios = SMOKE_SCENARIOS if smoke else FULL_SCENARIOS
    if smoke:
        arrivals = min(arrivals, 40)
        loads = (0.4, 0.8)
    rows = []
    for name in scenarios:
        sc = make_scenario(name, seed=0)
        # The LM mix's mean service time (~6.5 s) is huge next to its
        # nominal inter-arrival gap, so the no-drain loop's backlog-growth
        # signal needs a longer stream to clear the divergence threshold.
        n_arr = arrivals * 2 if name.startswith("us-backbone") else arrivals
        for load in loads:
            rate = sc.nominal_rate(load)
            horizon = n_arr / rate
            row = {"scenario": sc.name, "load": load, "rate_per_s": rate,
                   "mean_service_s": sc.mean_service_s}
            for mode, drain in (("drain", True), ("nodrain", False)):
                tr = run_online(sc, horizon=horizon, seed=seed, rate=rate,
                                drain_queues=drain)
                s = tr.summary()
                row[mode] = s
            row["drain_bounded"] = (
                row["drain"]["backlog_growth"] <= DRAIN_BOUNDED_MAX_GROWTH)
            row["nodrain_diverges"] = (
                row["nodrain"]["backlog_growth"] >= NODRAIN_MIN_GROWTH)
            rows.append(row)
            if verbose:
                d, nd = row["drain"], row["nodrain"]
                print(f"{sc.name:28s} load {load:.1f}: "
                      f"p99 {d['p99_latency_s']:8.3f}s vs {nd['p99_latency_s']:8.3f}s  "
                      f"backlog growth {d['backlog_growth']:.2f} vs "
                      f"{nd['backlog_growth']:.2f}  "
                      f"bounded={row['drain_bounded']} "
                      f"diverges={row['nodrain_diverges']}", flush=True)
    return rows


def _fluid_matches_seed() -> bool:
    """Default-mode (fluid) online trajectory, bit-compared against the
    pre-ledger capture on paper-small."""
    from benchmarks.common import (FLUID_GOLD_ARRIVALS, FLUID_GOLD_BACKLOGS,
                                   FLUID_GOLD_LATENCIES, FLUID_GOLD_LOAD,
                                   FLUID_GOLD_SCENARIO, FLUID_GOLD_SEED)
    from repro.scenarios import make_scenario
    from repro.serving.online import run_online

    sc = make_scenario(FLUID_GOLD_SCENARIO, seed=0)
    rate = sc.nominal_rate(FLUID_GOLD_LOAD)
    tr = run_online(sc, horizon=FLUID_GOLD_ARRIVALS / rate,
                    seed=FLUID_GOLD_SEED, rate=rate)
    return (tr.backlogs.tolist() == FLUID_GOLD_BACKLOGS
            and tr.latencies.tolist() == FLUID_GOLD_LATENCIES)


def _bound_violations(actual: np.ndarray, bound: np.ndarray) -> dict:
    excess = actual - bound
    viol = actual > bound * (1 + BOUND_TOL) + 1e-9
    return {
        "requests": int(bound.size),
        "violations": int(viol.sum()),
        "max_excess_s": float(excess.max()) if excess.size else 0.0,
        "mean_headroom_s": float(np.maximum(bound - actual, 0.0).mean())
        if excess.size else 0.0,
    }


def run_fidelity(*, smoke: bool = False, arrivals: int = 40, seed: int = 7,
                 verbose: bool = True) -> dict:
    """Fluid vs exact drain vs ground-truth replay, per scenario."""
    from repro.core import completions as C
    from repro.scenarios import make_scenario
    from repro.serving.online import run_online

    scenarios = FIDELITY_SMOKE_SCENARIOS if smoke else FIDELITY_FULL_SCENARIOS
    if smoke:
        arrivals = min(arrivals, 30)
    rows = []
    for name in scenarios:
        sc = make_scenario(name, seed=0)
        rate = sc.nominal_rate(FIDELITY_LOAD)
        horizon = arrivals / rate
        kw = dict(horizon=horizon, seed=seed, rate=rate,
                  track_commits=True, finish=True)
        fluid = run_online(sc, drain="fluid", **kw)
        exact = run_online(sc, drain="exact", **kw)
        # Same plans, exact accounting: the drain-semantics gap in isolation.
        exact_backlogs = C.exact_backlog_trace(sc.topology, fluid.commit_log,
                                               fluid.times)
        fluid_backlogs = np.array([r.backlog_before for r in fluid.records])
        gap = exact_backlogs - fluid_backlogs
        # Claimed bounds vs actual completions (full-horizon event replay).
        fluid_gt = _bound_violations(fluid.actual_latencies(),
                                     fluid.latencies)
        exact_gt = _bound_violations(exact.actual_latencies(),
                                     exact.latencies)
        # Incremental exact drain vs one-shot replay of the same commits.
        replay_diff = max((abs(exact.completions[n]
                               - exact.replay_completions[n])
                           for n in exact.completions), default=0.0)
        row = {
            "scenario": sc.name,
            "load": FIDELITY_LOAD,
            "arrivals": len(fluid.records),
            "fluid": fluid.summary(),
            "exact": exact.summary(),
            "backlog_gap_mean_s": float(gap.mean()),
            "backlog_gap_max_s": float(gap.max()),
            "backlog_gap_vs_fluid_mean": float(
                gap.mean() / max(fluid_backlogs.mean(), 1e-12)),
            "fluid_never_pessimistic": bool((gap >= -1e-6).all()),
            "fluid_vs_ground_truth": fluid_gt,
            "exact_vs_ground_truth": exact_gt,
            "exact_bounds_hold": exact_gt["violations"] == 0,
            "exact_replay_max_diff_s": float(replay_diff),
            "exact_matches_replay": bool(replay_diff <= 1e-6),
        }
        rows.append(row)
        if verbose:
            print(f"fidelity {sc.name:28s}: backlog gap mean "
                  f"{row['backlog_gap_mean_s']:.4f}s "
                  f"({100 * row['backlog_gap_vs_fluid_mean']:.0f}% of fluid) "
                  f"fluid bound violations "
                  f"{fluid_gt['violations']}/{fluid_gt['requests']} "
                  f"(max excess {fluid_gt['max_excess_s']:.4f}s)  "
                  f"exact holds={row['exact_bounds_hold']} "
                  f"replay diff {replay_diff:.2e}", flush=True)
    out = {
        "load": FIDELITY_LOAD,
        "rows": rows,
        "fluid_matches_seed": _fluid_matches_seed(),
        "all_exact_bounds_hold": all(r["exact_bounds_hold"] for r in rows),
        "all_exact_match_replay": all(r["exact_matches_replay"]
                                      for r in rows),
        "any_fluid_bound_violation": any(
            r["fluid_vs_ground_truth"]["violations"] > 0 for r in rows),
    }
    if verbose:
        print(f"fluid_matches_seed={out['fluid_matches_seed']} "
              f"all_exact_bounds_hold={out['all_exact_bounds_hold']} "
              f"all_exact_match_replay={out['all_exact_match_replay']}",
              flush=True)
    return out


def main() -> None:
    from repro.launch import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny streams, 2 scenarios (the CI gate)")
    ap.add_argument("--arrivals", type=int, default=80)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=str(pathlib.Path(__file__).parent
                                         / "BENCH_online.json"))
    args = ap.parse_args()

    rows = run(smoke=args.smoke, arrivals=args.arrivals, seed=args.seed)
    fidelity = run_fidelity(smoke=args.smoke, seed=args.seed + 6)
    record = {
        "benchmark": "online_serving",
        "smoke": args.smoke,
        "static_bounds_match": _static_bounds_match(),
        "rows": rows,
        "all_drain_bounded": all(r["drain_bounded"] for r in rows),
        "all_nodrain_diverge": all(r["nodrain_diverges"] for r in rows),
        "fidelity": fidelity,
    }
    pathlib.Path(args.out).write_text(json.dumps(record, indent=2))
    print(f"wrote {args.out}")
    print(f"static_bounds_match={record['static_bounds_match']} "
          f"all_drain_bounded={record['all_drain_bounded']} "
          f"all_nodrain_diverge={record['all_nodrain_diverge']}")
    if not record["static_bounds_match"]:
        raise SystemExit("static greedy path no longer bit-identical to seed")
    if not fidelity["fluid_matches_seed"]:
        raise SystemExit("default (fluid) online trajectory no longer "
                         "bit-identical to the pre-ledger capture")
    if args.smoke and not record["all_drain_bounded"]:
        raise SystemExit("draining scheduler failed to keep backlog bounded")
    if args.smoke and not record["all_nodrain_diverge"]:
        raise SystemExit("no-drain baseline unexpectedly stayed bounded")
    if args.smoke and not fidelity["all_exact_bounds_hold"]:
        raise SystemExit("exact-drain bounds were violated by the ground-"
                         "truth replay")
    if args.smoke and not fidelity["all_exact_match_replay"]:
        raise SystemExit("incremental exact drain diverged from the one-"
                         "shot full-horizon replay")


if __name__ == "__main__":
    main()
