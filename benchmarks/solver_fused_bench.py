"""Fused single-dispatch greedy solver: parity, dispatch accounting, scaling.

  PYTHONPATH=src python benchmarks/solver_fused_bench.py [--smoke] [--out PATH]

Four measurements around ``greedy_route`` (the on-device ``lax.scan`` round
loop) against ``greedy_route_ref`` (the host-driven loop it replaced, kept
as the parity reference):

  * ``parity``  — over a seeded scenario catalog, the fused solver must
    reproduce the reference **bit-for-bit**: round order, assignments,
    bounds, committed queues, and extracted paths — at the fresh state AND
    at the queued state left by committing the first plan (queued edge
    weights are where an FMA-contraction ulp would flip argmin ties).
    ``fused_matches_ref`` is the global flag CI gates on.
  * ``solve_scaling`` — warm per-solve wall vs batch width J, fused vs
    reference, with honest dispatch accounting: the fused solve is one
    device program per solve (``meta["dispatches"] == 1``) regardless of
    J, while the reference pays J closure builds + J round dispatches.
  * ``window_scaling`` — cross-arrival batching: W queued windows solved
    by one ``solve_fused`` multi-window dispatch vs W sequential fused
    solves threading the committed queues by hand.
  * ``end_to_end`` — the full exact-drain online serving loop of
    ``drain_bench`` (same scenario, arrival process, seed and phases),
    now with the fused solver, against the arr/s its ``BENCH_drain.json``
    recorded for the identical drive with the pre-fused solver (the
    1.15 arr/s us-backbone:lm baseline).  ``end_to_end_5x`` is the
    headline acceptance flag: >= 5x sustained arrivals/sec.

``--smoke`` (tiny catalog + a short paper-small end-to-end pair driven
both ways) is the CI gate: it fails on any parity regression.
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

# Parity catalog: (scenario, jobs-per-window).  Every entry is checked at
# the fresh state and at the queued state its own first commit produces.
SMOKE_PARITY = [("paper-small", 4), ("star", 4)]
FULL_PARITY = [("paper-small", 4), ("paper-small", 7),   # 7: odd-J pad path
               ("star", 4), ("edge-cloud:synthetic", 4),
               ("random-geometric", 4), ("us-backbone:lm", 8)]

SMOKE_JOBS = (2, 4)
FULL_JOBS = (4, 8, 16, 32)
SMOKE_WINDOWS = (1, 2)
FULL_WINDOWS = (1, 2, 4, 8)

# drain_bench's end-to-end cases: (scenario, arrivals, batch, load).  The
# full case is the BENCH_drain.json headline row (seed 5, poisson).
SMOKE_E2E = ("paper-small", 10, 2, 1.2)
FULL_E2E = ("us-backbone:lm", 160, 32, 1.5)
DRAIN_BASELINE_FALLBACK = 1.1453   # BENCH_drain.json us-backbone:lm arr/s
E2E_TARGET_SPEEDUP = 5.0


def _plans_bitwise(a, b) -> bool:
    return (a.order.tolist() == b.order.tolist()
            and np.array_equal(np.asarray(a.assign), np.asarray(b.assign))
            and (np.asarray(a.bounds).tolist()
                 == np.asarray(b.bounds).tolist())
            and np.array_equal(np.asarray(a.net.q_node),
                               np.asarray(b.net.q_node))
            and np.array_equal(np.asarray(a.net.q_link),
                               np.asarray(b.net.q_link))
            and a.paths == b.paths)


def _parity_case(name: str, jobs_per: int, *, seed: int) -> dict:
    from repro.core import greedy, jobs as J
    from repro.scenarios import make_scenario

    sc = make_scenario(name, seed=0)
    rng = np.random.default_rng(seed)
    net = sc.topology.view()
    row = {"scenario": name, "jobs": jobs_per}
    for state in ("fresh", "queued"):
        batch = J.batch_jobs(sc.sample_jobs(rng, jobs_per),
                             pad_to=sc.max_layers)
        fused = greedy.greedy_route(net, batch, extract_paths=True)
        ref = greedy.greedy_route_ref(net, batch, extract_paths=True)
        row[f"{state}_ok"] = _plans_bitwise(fused, ref)
        net = fused.net   # the committed queues seed the queued-state check
    row["ok"] = row["fresh_ok"] and row["queued_ok"]
    return row


def _time_best(fn, repeat: int) -> float:
    fn()   # warm: jit compilation keys on shapes, not values
    best = float("inf")
    for _ in range(max(repeat, 1)):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _solve_scaling(name: str, sizes, *, seed: int, repeat: int,
                   verbose: bool) -> list[dict]:
    from repro.core import greedy, jobs as J
    from repro.core import shortest_path as SP
    from repro.scenarios import make_scenario

    sc = make_scenario(name, seed=0)
    rng = np.random.default_rng(seed)
    net = sc.topology.view()
    rows = []
    for n in sizes:
        batch = J.batch_jobs(sc.sample_jobs(rng, n), pad_to=sc.max_layers)
        fused_s = _time_best(
            lambda: np.asarray(greedy.greedy_route(net, batch).bounds),
            repeat)
        ref_s = _time_best(
            lambda: np.asarray(greedy.greedy_route_ref(net, batch).bounds),
            repeat)
        plan = greedy.greedy_route(net, batch)
        SP.reset_closure_build_count()
        greedy.greedy_route_ref(net, batch)
        row = {
            "scenario": name,
            "jobs": n,
            "fused_ms": fused_s * 1e3,
            "ref_ms": ref_s * 1e3,
            "speedup": ref_s / fused_s,
            "dispatches": plan.meta["dispatches"],
            "rounds_per_dispatch": plan.meta["rounds_per_dispatch"],
            "ref_closure_builds": SP.closure_build_count(),
        }
        rows.append(row)
        if verbose:
            print(f"  J={n:3d}: fused {row['fused_ms']:8.2f} ms "
                  f"(1 dispatch, {row['rounds_per_dispatch']} rounds)  "
                  f"ref {row['ref_ms']:8.2f} ms "
                  f"({row['ref_closure_builds']} closure builds)  "
                  f"{row['speedup']:5.2f}x", flush=True)
    return rows


def _window_scaling(name: str, widths, *, jobs_per: int, seed: int,
                    repeat: int, verbose: bool) -> list[dict]:
    from repro.core import greedy, jobs as J, solvers
    from repro.scenarios import make_scenario

    sc = make_scenario(name, seed=0)
    rng = np.random.default_rng(seed)
    net = sc.topology.view()
    windows = [J.batch_jobs(sc.sample_jobs(rng, jobs_per),
                            pad_to=sc.max_layers) for _ in range(max(widths))]
    rows = []
    for w in widths:
        batches = windows[:w]

        def fused():
            plans = solvers.solve_fused(net, batches, pad_to=sc.max_layers)
            np.asarray(plans[-1].bounds)

        def sequential():
            cur = net
            for b in batches:
                p = greedy.greedy_route(cur, b)
                cur = p.net
            np.asarray(p.bounds)

        fused_s = _time_best(fused, repeat)
        seq_s = _time_best(sequential, repeat)
        row = {
            "scenario": name,
            "windows": w,
            "jobs_per_window": jobs_per,
            "fused_ms": fused_s * 1e3,
            "sequential_ms": seq_s * 1e3,
            "speedup": seq_s / fused_s,
            "dispatches": 1,
            "sequential_dispatches": w,
        }
        rows.append(row)
        if verbose:
            print(f"  W={w}: fused {row['fused_ms']:8.2f} ms (1 dispatch)  "
                  f"sequential {row['sequential_ms']:8.2f} ms "
                  f"({w} dispatches)  {row['speedup']:5.2f}x", flush=True)
    return rows


def _e2e_drive(name: str, *, arrivals: int, batch: int, load: float,
               seed: int, method: str = "greedy") -> dict:
    """drain_bench's full exact-drain session, solver method selectable."""
    from repro.core import arrivals as A
    from repro.scenarios import make_scenario
    from repro.serving.online import OnlineScheduler

    sc = make_scenario(name, seed=0)
    rate = sc.nominal_rate(load)
    rng = np.random.default_rng(seed)
    times = A.make_process("poisson", rate=rate)(rng, arrivals / rate)
    sched = OnlineScheduler(sc.topology, drain="exact", sim_engine="indexed",
                            track_commits=True, method=method)
    t0 = time.time()
    for t in times:
        sched.submit_jobs(float(t), sc.sample_jobs(rng, batch),
                          pad_to=sc.max_layers)
    t_submit = time.time() - t0
    t0 = time.time()
    sched.finish()
    t_finish = time.time() - t0
    t0 = time.time()
    sched.replay_ground_truth()
    t_replay = time.time() - t0
    wall = t_submit + t_finish + t_replay
    return {
        "arrivals": len(times),
        "wall_s": wall,
        "submit_s": t_submit,
        "finish_s": t_finish,
        "replay_s": t_replay,
        "arrivals_per_s": len(times) / wall,
    }


def _drain_baseline(name: str) -> tuple[float, str]:
    """arr/s BENCH_drain.json recorded for this scenario's identical drive
    with the pre-fused solver (fallback: the committed headline number)."""
    path = pathlib.Path(__file__).parent / "BENCH_drain.json"
    try:
        for r in json.loads(path.read_text())["rows"]:
            if r["scenario"] == name:
                return (float(r["loop"]["indexed"]["arrivals_per_s"]),
                        "BENCH_drain.json")
    except (OSError, KeyError, ValueError):
        pass
    return DRAIN_BASELINE_FALLBACK, "fallback"


def _end_to_end(smoke: bool, *, seed: int, repeat: int,
                verbose: bool) -> dict:
    name, arrivals, batch, load = SMOKE_E2E if smoke else FULL_E2E
    kw = dict(arrivals=arrivals, batch=batch, load=load, seed=seed)
    # Untimed warm-up over the identical stream (jit shapes), then the
    # best of ``repeat`` timed drives (same discipline as the other
    # benches — a single ~30 s session carries scheduler noise).
    _e2e_drive(name, **kw)
    fused = max((_e2e_drive(name, **kw) for _ in range(max(repeat, 1))),
                key=lambda r: r["arrivals_per_s"])
    out = {"scenario": name, "arrivals": arrivals, "batch": batch,
           "load": load, "fused": fused}
    if smoke:
        # Small enough to drive the reference solver directly — the smoke
        # speedup is self-contained rather than vs a recorded baseline.
        _e2e_drive(name, method="greedy_ref", **kw)
        ref = _e2e_drive(name, method="greedy_ref", **kw)
        out["ref"] = ref
        out["baseline_arr_per_s"] = ref["arrivals_per_s"]
        out["baseline_source"] = "greedy_ref (same drive)"
    else:
        base, src = _drain_baseline(name)
        out["baseline_arr_per_s"] = base
        out["baseline_source"] = src
    out["speedup"] = fused["arrivals_per_s"] / out["baseline_arr_per_s"]
    out["end_to_end_5x"] = bool(out["speedup"] >= E2E_TARGET_SPEEDUP)
    if verbose:
        print(f"  end-to-end {name}: {fused['arrivals_per_s']:7.2f} arr/s "
              f"(submit {fused['submit_s']:.1f}s) vs baseline "
              f"{out['baseline_arr_per_s']:.2f} arr/s "
              f"[{out['baseline_source']}]  {out['speedup']:5.2f}x  "
              f">=5x: {out['end_to_end_5x']}", flush=True)
    return out


def run(*, smoke: bool = False, seed: int = 5, repeat: int = 3,
        verbose: bool = True) -> dict:
    parity_cases = SMOKE_PARITY if smoke else FULL_PARITY
    parity = [_parity_case(n, j, seed=seed + i)
              for i, (n, j) in enumerate(parity_cases)]
    matches = all(r["ok"] for r in parity)
    if verbose:
        for r in parity:
            print(f"  parity {r['scenario']:24s} J={r['jobs']:2d}: "
                  f"fresh={r['fresh_ok']} queued={r['queued_ok']}",
                  flush=True)
    scale_name = "paper-small" if smoke else "us-backbone:lm"
    solve_rows = _solve_scaling(scale_name, SMOKE_JOBS if smoke else FULL_JOBS,
                                seed=seed, repeat=repeat, verbose=verbose)
    window_rows = _window_scaling(scale_name,
                                  SMOKE_WINDOWS if smoke else FULL_WINDOWS,
                                  jobs_per=2 if smoke else 8, seed=seed,
                                  repeat=repeat, verbose=verbose)
    e2e = _end_to_end(smoke, seed=seed, repeat=repeat, verbose=verbose)
    out = {
        "benchmark": "solver_fused",
        "smoke": smoke,
        "parity": parity,
        "fused_matches_ref": matches,
        "solve_scaling": solve_rows,
        "window_scaling": window_rows,
        "end_to_end": e2e,
        "end_to_end_5x": e2e["end_to_end_5x"],
    }
    if verbose:
        print(f"fused_matches_ref={matches} "
              f"end_to_end {e2e['speedup']:.2f}x "
              f"(target >= {E2E_TARGET_SPEEDUP:.0f}x on the full case)",
              flush=True)
    return out


def main() -> None:
    from repro.launch import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small catalog + short end-to-end pair (the CI "
                         "bit-parity gate)")
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--repeat", type=int, default=3)
    ap.add_argument("--out", default=str(pathlib.Path(__file__).parent
                                         / "BENCH_solver.json"))
    args = ap.parse_args()
    record = run(smoke=args.smoke, seed=args.seed, repeat=args.repeat)
    pathlib.Path(args.out).write_text(json.dumps(record, indent=2))
    print(f"wrote {args.out}")
    if not record["fused_matches_ref"]:
        raise SystemExit("fused solver diverged bitwise from "
                         "greedy_route_ref")


if __name__ == "__main__":
    main()
