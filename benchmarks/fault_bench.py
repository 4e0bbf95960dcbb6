"""Fault recovery: requeue / migrate / lost vs a clairvoyant oracle.

  PYTHONPATH=src python benchmarks/fault_bench.py [--smoke] [--out PATH]

Each scenario is driven through the serial online loop (exact drain +
ground-truth commit log) under a ``transient-node`` fault schedule — the
highest-capacity interior compute node fails mid-horizon and recovers
later — once per recovery policy:

  * ``requeue``  — stranded jobs re-planned onto the surviving topology,
    re-transferring from the node holding their last completed layer;
  * ``migrate``  — stranded jobs' remaining layers moved to one chosen
    node (the ``"migrate"`` solver's argmin placement);
  * ``lost``     — stranded work shed and accounted.

The baseline is a **clairvoyant oracle**: the identical arrival stream
solved against the post-failure topology from t=0.  It knows the victim
will fail, never places work there, and therefore pays zero disruption —
but it also forgoes the victim's capacity for the whole horizon (even
after recovery), so a good reactive policy can beat it outside the
outage.  ``p99_vs_oracle`` is each policy's actual-latency p99 ratio
against it — the price of *not* knowing the future under that policy.

``BENCH_fault.json`` records, per scenario x policy: completed / requeued
/ lost counts (lost by reason), p50/p99 actual latency, max backlog and
realized backlog growth, plus two boolean gates CI enforces via
``--smoke``:

  * ``replay_match`` — the exact drain's completion times and the
    piecewise commit-log replay agree to ``REPLAY_EPS_S`` through the
    whole failure/recovery sequence (the tentpole's ground-truth
    contract);
  * ``bounded`` (every policy, sub-capacity) — after the recovery event
    the backlog is under control: either the per-entry backlog trend from
    the first to the last post-recovery commit is negative
    (``post_recovery_drain_s_per_s < 0`` — a real queue, draining) or the
    final post-recovery backlog sits under one mean service time (no
    queue ever formed; sub-mean-service wobble is arrival noise, not
    growth).  Either way a transient outage must not tip a stable system
    into divergence.  (The half-over-half ``backlog_growth`` of the
    stability benches is reported but not gated here — a mid-horizon
    outage puts its peak wherever the fault lands, which makes that
    ratio noisy by construction.)
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

SMOKE_CASES = [
    dict(name="edge-cloud", arrivals=32, load=0.85),
]
FULL_CASES = [
    dict(name="edge-cloud", arrivals=48, load=0.85),
    dict(name="edge-cloud:synthetic", arrivals=48, load=0.75),
    dict(name="paper-small", arrivals=48, load=0.75),
]

POLICIES = ("requeue", "migrate", "lost")
REPLAY_EPS_S = 1e-6          # absolute agreement bar for replay parity


def _parity(tr) -> tuple[bool, float]:
    """Exact-drain completions vs piecewise commit-log replay."""
    cc, rr = tr.completions or {}, tr.replay_completions or {}
    if set(cc) != set(rr):
        return False, float("inf")
    gap = max((abs(cc[n] - rr[n]) for n in cc), default=0.0)
    return gap <= REPLAY_EPS_S, gap


def _metrics(tr, recover_t: float | None = None) -> dict:
    s = tr.summary()
    act = tr.actual_latencies()
    match, gap = _parity(tr)
    lost_by: dict[str, int] = {}
    for _, why in tr.lost:
        lost_by[why] = lost_by.get(why, 0) + 1
    requeued = sum(1 for r in tr.records for n in r.names if "#r" in n)
    drain, final_post = None, None
    if recover_t is not None:
        post = [(r.time, r.backlog_after) for r in tr.records
                if r.time >= recover_t]
        if post:
            final_post = post[-1][1]
        if len(post) >= 2:
            (t0, b0), (t1, b1) = post[0], post[-1]
            drain = (b1 - b0) / max(t1 - t0, 1e-9)
    return {
        "completed": len(tr.completions or {}),
        "requeued": requeued,
        "lost": len(tr.lost),
        "lost_by_reason": lost_by,
        "p50_actual_s": float(np.percentile(act, 50)) if act.size else None,
        "p99_actual_s": float(np.percentile(act, 99)) if act.size else None,
        "max_backlog_s": s["max_backlog_s"],
        "backlog_growth": s["backlog_growth"],
        "post_recovery_drain_s_per_s": drain,
        "post_recovery_final_backlog_s": final_post,
        "replay_match": match,
        "replay_gap_s": gap,
    }


def _drive(name: str, *, horizon: float, rate: float, seed: int,
           fault_schedule, recovery: str = "requeue"):
    """One fresh serial online session (identical rng => identical jobs)."""
    from repro.scenarios import make_scenario
    from repro.serving.online import run_online

    return run_online(make_scenario(name, seed=0), horizon=horizon,
                      rate=rate, seed=seed, drain="exact",
                      track_commits=True, finish=True,
                      fault_schedule=fault_schedule, recovery=recovery)


def _bench_case(case: dict, *, seed: int, verbose: bool) -> dict:
    from repro.scenarios import make_scenario
    from repro.serving import faults as F

    name, arrivals, load = case["name"], case["arrivals"], case["load"]
    sc = make_scenario(name, seed=0)
    rate = sc.nominal_rate(load)
    horizon = arrivals / rate
    schedule = F.make_fault_schedule("transient-node", sc, horizon,
                                     seed=seed)
    victim = schedule.events[0].node
    recover_t = max(e.time for e in schedule)

    # Clairvoyant oracle: victim down from t=0 — it avoids the node
    # entirely, so no work is ever stranded and no policy runs.
    oracle_tr = _drive(name, horizon=horizon, rate=rate, seed=seed,
                       fault_schedule=F.FaultSchedule(
                           (F.node_fail(0.0, victim),)), recovery="lost")
    oracle = _metrics(oracle_tr)

    rows = {}
    for policy in POLICIES:
        tr = _drive(name, horizon=horizon, rate=rate, seed=seed,
                    fault_schedule=schedule, recovery=policy)
        m = _metrics(tr, recover_t)
        if oracle["p99_actual_s"] and m["p99_actual_s"] is not None:
            m["p99_vs_oracle"] = m["p99_actual_s"] / oracle["p99_actual_s"]
        rows[policy] = m
        if verbose:
            print(f"  {policy:8s} done={m['completed']:3d} "
                  f"requeued={m['requeued']} lost={m['lost']} "
                  f"p99={m['p99_actual_s']:.2f}s "
                  f"(x{m.get('p99_vs_oracle', float('nan')):.2f} oracle) "
                  f"drain={m['post_recovery_drain_s_per_s']} "
                  f"replay={m['replay_match']}", flush=True)

    mean_service_s = load / rate

    def _ok(r: dict) -> bool:
        drain, final = (r["post_recovery_drain_s_per_s"],
                        r["post_recovery_final_backlog_s"])
        if drain is not None and drain < 0:
            return True          # a real queue, draining post-recovery
        return final is not None and final <= mean_service_s

    sub_capacity = load < 1.0
    bounded = all(_ok(r) for r in rows.values()) if sub_capacity else True
    out = {
        "scenario": name,
        "arrivals": arrivals,
        "load": load,
        "rate_per_s": rate,
        "horizon_s": horizon,
        "victim": int(victim),
        "fault_events": [(e.time, e.kind, e.node) for e in schedule],
        "oracle": oracle,
        "policies": rows,
        "all_replay_match": (oracle["replay_match"]
                             and all(r["replay_match"]
                                     for r in rows.values())),
        "requeue_bounded": bounded,
    }
    if verbose:
        print(f"{name:24s} oracle p99={oracle['p99_actual_s']:.2f}s "
              f"replay={out['all_replay_match']} "
              f"bounded={out['requeue_bounded']}", flush=True)
    return out


def run(*, smoke: bool = False, seed: int = 7,
        verbose: bool = True) -> dict:
    cases = SMOKE_CASES if smoke else FULL_CASES
    rows = [_bench_case(case, seed=seed, verbose=verbose)
            for case in cases]
    out = {
        "benchmark": "fault",
        "smoke": smoke,
        "replay_eps_s": REPLAY_EPS_S,
        "rows": rows,
        "all_replay_match": all(r["all_replay_match"] for r in rows),
        "all_requeue_bounded": all(r["requeue_bounded"] for r in rows),
    }
    if verbose:
        print(f"all_replay_match={out['all_replay_match']} "
              f"all_requeue_bounded={out['all_requeue_bounded']}",
              flush=True)
    return out


def main() -> None:
    from repro.launch import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="1 small scenario (the CI gate: replay parity + "
                         "requeue bounded backlog)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", default=str(pathlib.Path(__file__).parent
                                         / "BENCH_fault.json"))
    args = ap.parse_args()
    record = run(smoke=args.smoke, seed=args.seed)
    pathlib.Path(args.out).write_text(json.dumps(record, indent=2))
    print(f"wrote {args.out}")
    if not record["all_replay_match"]:
        raise SystemExit("piecewise replay diverged from the exact drain "
                         "through a failure/recovery sequence")
    if not record["all_requeue_bounded"]:
        raise SystemExit("requeue backlog not bounded after a transient "
                         "failure at sub-capacity load")


if __name__ == "__main__":
    main()
