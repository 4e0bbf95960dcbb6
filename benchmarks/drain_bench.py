"""Exact-drain throughput: indexed event engine vs the reference loop.

  PYTHONPATH=src python benchmarks/drain_bench.py [--smoke] [--out PATH]

For each scenario the full online serving loop runs twice in exact-drain
mode — once on the persistent indexed engine (``sim_engine="indexed"``,
the default), once on the seed linear-scan loop (``"ref"``) — with
identical arrivals, jobs, and plans.  The loop is the deployment pipeline
end to end: per-arrival drain + solve + ledger commit, then the end-of-run
accounting exact mode exists for (``finish()`` serves the residual ledger,
``replay_ground_truth()`` replays the full commit log).  The replay phase
doubles as the drain-only measurement (pure event machinery, solver
excluded); a separate micro-timing covers the per-epoch exact backlog
trace (single forward pass vs the seed's per-sample rescan).

``BENCH_drain.json`` records, per scenario:

  * ``loop``    — wall/arrivals-per-sec for both engines + ``speedup``,
  * ``replay``  — drain-only wall/events-per-sec + ``speedup`` (the pure
                  event-machinery ratio, solver excluded),
  * ``trace``   — exact_backlog_trace wall + ``speedup``,
  * ``indexed_matches_ref`` — same-ledger parity: the indexed engine's
    replay of the ref session's own commit log reproduces the ref loop's
    ground truth within the event-time tolerance discipline (rtol/atol
    1e-9 on completion instants; a rare bounded fraction of knife-edge
    preemption races is allowed — see ``RACE_MAX_FRAC`` — and reported as
    ``replay_races``), and both engines' backlog traces of one log agree
    (the ref trace rounds queues through float32, hence its atol).  The
    two sessions' end-to-end completions are reported as an informational
    ``trajectory_max_diff_s`` only — independent solves may flip a solver
    argmin tie when materialized queues differ in the last ulp, and
    everything downstream of a flipped plan legitimately differs.

plus global flags:

  * ``all_indexed_match_ref`` — parity on every scenario (CI gates on it),
  * ``simulate_unchanged``    — one-shot ``schedule.simulate`` still runs
    the reference loop by default, bit-identical results.

``--smoke`` (2 small scenarios, short streams) is the CI regression gate:
it fails on any parity regression.  Full mode adds ``us-backbone:lm`` at
peak (overloaded-burst) traffic — the scale the paper's §V evaluations
target — where the headline ``loop``/``replay`` speedups are measured.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_ROOT))
sys.path.insert(0, str(_ROOT / "src"))

import numpy as np

# (scenario, arrivals, batch, load): load > 1 is a sustained burst — the
# regime that builds a deep committed backlog, which is exactly what makes
# the reference loop's per-event task rescan quadratic in practice.
SMOKE_CASES = [
    ("paper-small", 10, 2, 1.2),
    ("star", 10, 2, 1.2),
]
FULL_CASES = [
    ("paper-small", 40, 4, 1.2),
    ("star", 40, 4, 1.2),
    ("edge-cloud:synthetic", 40, 4, 1.2),
    ("random-geometric", 40, 4, 1.2),
    ("us-backbone:lm", 160, 32, 1.5),
]

COMPLETION_RTOL = 1e-9
COMPLETION_ATOL = 1e-9
# Preempt-resume with strict priorities is *discontinuous* at event-time
# coincidences: when two independent events land within float-accumulation
# distance (~1e-10 relative), which fires first depends on the engine's
# arithmetic order, and the losing side shifts by a whole preemption
# quantum — the reference loop itself flips the same way under a one-ulp
# input perturbation.  Parity therefore requires the *bulk* of completions
# at float precision and allows a rare bounded fraction of such races.
RACE_MAX_FRAC = 0.005
TRACE_RTOL = 1e-6
TRACE_ATOL = 1e-4   # ref trace rounds queues through float32; indexed is f64


def _drive(name: str, engine: str, *, arrivals: int, batch: int, load: float,
           seed: int) -> dict:
    """One full exact-drain serving session; phase walls + artifacts."""
    from repro.core import arrivals as A
    from repro.scenarios import make_scenario
    from repro.serving.online import OnlineScheduler

    # Fresh scenario per drive: identical rng stream => identical jobs AND
    # identical job names across engines (names key the completions).
    sc = make_scenario(name, seed=0)
    rate = sc.nominal_rate(load)
    rng = np.random.default_rng(seed)
    times = A.make_process("poisson", rate=rate)(rng, arrivals / rate)
    sched = OnlineScheduler(sc.topology, drain="exact", sim_engine=engine,
                            track_commits=True)
    t0 = time.time()
    for t in times:
        sched.submit_jobs(float(t), sc.sample_jobs(rng, batch),
                          pad_to=sc.max_layers)
    t_submit = time.time() - t0
    t0 = time.time()
    completions = sched.finish()
    t_finish = time.time() - t0
    t0 = time.time()
    replay = sched.replay_ground_truth()
    t_replay_gt = time.time() - t0
    return {
        "arrivals": len(times),
        "jobs": len(completions),
        "wall_s": t_submit + t_finish + t_replay_gt,
        "submit_s": t_submit,
        "finish_s": t_finish,
        "replay_s": t_replay_gt,
        "completions": completions,
        "replay_completions": replay,
        "commit_log": sched.commit_log,
        "trace": sched.trace,
        "topology": sc.topology,
    }


def _max_diff(a: dict, b: dict) -> float:
    if a.keys() != b.keys():
        return float("inf")
    return max((abs(a[k] - b[k]) for k in a), default=0.0)


def _completion_parity(a: dict, b: dict) -> dict:
    """Per-job agreement stats + the race-tolerant verdict (see
    RACE_MAX_FRAC)."""
    if a.keys() != b.keys():
        return {"max_diff_s": float("inf"), "races": -1, "race_frac": 1.0,
                "ok": False}
    diffs = np.array([abs(a[k] - b[k]) for k in a], np.float64)
    tol = np.array([COMPLETION_ATOL + COMPLETION_RTOL * abs(b[k])
                    for k in a], np.float64)
    races = int((diffs > tol).sum())
    frac = races / max(diffs.size, 1)
    return {
        "max_diff_s": float(diffs.max(initial=0.0)),
        "races": races,
        "race_frac": frac,
        "ok": bool(frac <= RACE_MAX_FRAC),
    }


def _close_traces(a: np.ndarray, b: np.ndarray) -> bool:
    return bool(np.allclose(a, b, rtol=TRACE_RTOL, atol=TRACE_ATOL))


def _bench_case(name: str, arrivals: int, batch: int, load: float, *,
                seed: int, verbose: bool) -> dict:
    from repro.core import completions as C

    # Untimed warm-up over the *same* arrival stream: jit compilation is
    # keyed by data-dependent shapes (deduped closure rows, path segment
    # counts), so only an identical drive covers every shape the timed
    # runs will hit — compilation must not be charged to either engine.
    # The ref session's trajectory can diverge (solver argmin ties), so it
    # gets its own full warm-up at small scale; at large scale a short ref
    # drive covers the base shapes and any stray tie-flip compile (~1 s)
    # is noise against the multi-minute ref wall.
    _drive(name, "indexed", arrivals=arrivals, batch=batch, load=load,
           seed=seed)
    _drive(name, "ref", arrivals=arrivals if arrivals * batch <= 1000
           else 8, batch=batch, load=load, seed=seed)
    runs = {eng: _drive(name, eng, arrivals=arrivals, batch=batch,
                        load=load, seed=seed) for eng in ("indexed", "ref")}
    idx, ref = runs["indexed"], runs["ref"]
    log, topo = idx["commit_log"], idx["topology"]
    # total stage-completion + arrival events of the horizon — the common
    # work denominator for events/sec on either engine
    events = sum(len(j.stages) + 1 for j in log.jobs)

    # Drain-only: the per-epoch exact backlog trace (the loop's replay_s
    # phase already times the full-horizon ground-truth replay).  At large
    # scale the epochs are subsampled — the ref trace re-serves the whole
    # horizon regardless, so the comparison is unchanged.
    sample_times = idx["trace"].times
    if sample_times.size > 64:
        sample_times = sample_times[:: int(np.ceil(sample_times.size / 64))]
    trace_wall, traces = {}, {}
    for eng in ("indexed", "ref"):
        t0 = time.time()
        traces[eng] = C.exact_backlog_trace(topo, log, sample_times,
                                            engine=eng)
        trace_wall[eng] = time.time() - t0

    # Parity is judged on the *same ledger*: the indexed engine replays the
    # ref run's own commit log and must reproduce the ref loop's recorded
    # ground truth; ditto the backlog traces above (both engines, one log).
    # The two *sessions'* completions are reported too, but only as an
    # informational trajectory diff — independent solves can flip an
    # argmin tie when the materialized queues differ in the last ulp, and
    # everything downstream of a flipped plan legitimately differs.
    idx_replay_of_ref_log, _ = C.run_to_completion(
        ref["topology"], ref["commit_log"], engine="indexed")
    parity = _completion_parity(idx_replay_of_ref_log,
                                ref["replay_completions"])
    trajectory_diff = _max_diff(idx["completions"], ref["completions"])
    trace_diff = float(np.abs(traces["indexed"] - traces["ref"]).max())
    matches = parity["ok"] and _close_traces(traces["indexed"],
                                             traces["ref"])
    row = {
        "scenario": name,
        "arrivals": idx["arrivals"],
        "batch": batch,
        "jobs": idx["jobs"],
        "load": load,
        "events": events,
        "loop": {
            eng: {"wall_s": r["wall_s"], "submit_s": r["submit_s"],
                  "finish_s": r["finish_s"], "replay_s": r["replay_s"],
                  "arrivals_per_s": r["arrivals"] / r["wall_s"]}
            for eng, r in runs.items()
        },
        "loop_speedup": ref["wall_s"] / idx["wall_s"],
        "replay": {eng: {"wall_s": r["replay_s"],
                         "events_per_s": events / r["replay_s"]}
                   for eng, r in runs.items()},
        "replay_speedup": ref["replay_s"] / idx["replay_s"],
        "trace_samples": int(sample_times.size),
        "trace": {eng: {"wall_s": w} for eng, w in trace_wall.items()},
        "trace_speedup": trace_wall["ref"] / trace_wall["indexed"],
        "replay_max_diff_s": parity["max_diff_s"],
        "replay_races": parity["races"],
        "replay_race_frac": parity["race_frac"],
        "trace_max_diff_s": trace_diff,
        "trajectory_max_diff_s": trajectory_diff,
        "indexed_matches_ref": bool(matches),
    }
    if verbose:
        print(f"{name:24s} jobs={row['jobs']:5d} events={events:6d}  "
              f"loop {row['loop_speedup']:5.2f}x "
              f"({row['loop']['ref']['wall_s']:7.2f}s -> "
              f"{row['loop']['indexed']['wall_s']:7.2f}s, "
              f"{row['loop']['indexed']['arrivals_per_s']:6.1f} arr/s)  "
              f"replay {row['replay_speedup']:6.1f}x "
              f"({row['replay']['indexed']['events_per_s']:9.0f} ev/s)  "
              f"trace {row['trace_speedup']:6.1f}x  "
              f"match={row['indexed_matches_ref']} "
              f"races={row['replay_races']}/{row['jobs']}", flush=True)
    return row


def _simulate_unchanged() -> bool:
    """One-shot simulate still runs the reference loop by default (bitwise),
    and the indexed engine agrees within tolerance."""
    from repro.core import jobs as J, schedule, solve
    from repro.scenarios import make_scenario

    sc = make_scenario("paper-small", seed=0)
    rng = np.random.default_rng(0)
    batch = J.batch_jobs(sc.sample_jobs(rng, 6), pad_to=sc.max_layers)
    net = sc.topology.view()
    plan = solve(net, batch, method="greedy")
    default = schedule.simulate(net, batch, plan)
    ref = schedule.simulate(net, batch, plan, engine="ref")
    idx = schedule.simulate(net, batch, plan, engine="indexed")
    bitwise = default.completion.tolist() == ref.completion.tolist()
    close = np.allclose(idx.completion, ref.completion, rtol=1e-9, atol=1e-9)
    return bool(bitwise and close)


def run(*, smoke: bool = False, seed: int = 5, verbose: bool = True) -> dict:
    cases = SMOKE_CASES if smoke else FULL_CASES
    rows = [_bench_case(name, arrivals, batch, load, seed=seed,
                        verbose=verbose)
            for name, arrivals, batch, load in cases]
    big = rows[-1]
    out = {
        "benchmark": "drain",
        "smoke": smoke,
        "rows": rows,
        "all_indexed_match_ref": all(r["indexed_matches_ref"] for r in rows),
        "simulate_unchanged": _simulate_unchanged(),
        "headline": {
            "scenario": big["scenario"],
            "loop_speedup": big["loop_speedup"],
            "replay_speedup": big["replay_speedup"],
            "arrivals_per_s_indexed":
                big["loop"]["indexed"]["arrivals_per_s"],
            "arrivals_per_s_ref": big["loop"]["ref"]["arrivals_per_s"],
            "events_per_s_indexed":
                big["replay"]["indexed"]["events_per_s"],
        },
    }
    if verbose:
        h = out["headline"]
        print(f"all_indexed_match_ref={out['all_indexed_match_ref']} "
              f"simulate_unchanged={out['simulate_unchanged']} "
              f"headline[{h['scenario']}]: loop {h['loop_speedup']:.2f}x, "
              f"replay {h['replay_speedup']:.1f}x, "
              f"{h['arrivals_per_s_indexed']:.1f} arr/s", flush=True)
    return out


def main() -> None:
    from repro.launch import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny streams, 2 scenarios (the CI parity gate)")
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--out", default=str(pathlib.Path(__file__).parent
                                         / "BENCH_drain.json"))
    args = ap.parse_args()
    record = run(smoke=args.smoke, seed=args.seed)
    pathlib.Path(args.out).write_text(json.dumps(record, indent=2))
    print(f"wrote {args.out}")
    if not record["all_indexed_match_ref"]:
        raise SystemExit("indexed engine diverged from the reference loop")
    if not record["simulate_unchanged"]:
        raise SystemExit("one-shot simulate results changed vs the "
                         "reference loop")


if __name__ == "__main__":
    main()
