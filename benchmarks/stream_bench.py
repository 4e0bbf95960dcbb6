"""Streaming pipeline throughput: sustained arr/s and p99 vs window (δ, B).

  PYTHONPATH=src python benchmarks/stream_bench.py [--smoke] [--out PATH]

For each scenario one bursty arrival stream is driven through the serving
stack twice over — once by the serial per-arrival loop (the pipeline at
δ=0, B=1: one solve per request) and once per (δ, B) batching-window
configuration (one padded batched solve per window) — with identical
arrivals, jobs, and drain semantics.  Wall-clock throughput (arrivals
processed per wall second — the metric ``BENCH_drain.json`` tracks for the
serial loop) is measured per configuration next to the *simulated* latency
the batching window costs: every request's recorded latency includes its
window residence, solver-queue wait and modeled solve latency, so a δ too
generous shows up as a p99 regression, not as a free lunch.

``BENCH_stream.json`` records, per scenario:

  * ``serial``  — the per-arrival baseline (wall arr/s, p50/p99, backlog),
  * ``grid``    — one row per (δ, B, solve_mode[, fuse_windows]): wall
    arr/s, ``speedup`` vs serial, ``p99_ratio`` (simulated p99 vs
    serial), sustained sim throughput, mean window occupancy,
    deferral/shed counts.  Rows with ``fuse_windows > 1`` drain that
    many queued windows per solver start as one fused multi-window
    dispatch (cross-arrival batching),
  * ``best_at_equal_p99`` — the fastest grid row whose p99 is within
    ``P99_EQUAL_TOL`` of serial; ``faster_at_equal_p99`` is the headline
    claim: the pipeline sustains strictly higher wall arr/s than the
    serial loop at equal p99,
  * ``pipeline_matches_serial`` — the correctness gate: at δ=0, B=1 and
    zero modeled solver latency the pipeline reproduces the serial
    ``run_online`` trace bit-identically (every record field except the
    measured solver wall, which is wall-clock),
  * ``drain_bounded`` — on sub-capacity cases, batching must not break
    stability.  Short bursty drives make the half-over-half backlog-max
    ratio noisy on heterogeneous job mixes (a heavy burst in one half
    moves it even for a perfectly drained system), so each windowed run
    is held to the serial loop's realized growth on the identical drive
    (small headroom), floored at the absolute ``online_bench`` bar.

plus global flags ``all_pipeline_match_serial`` / ``all_bounded`` (CI
gates on both via ``--smoke``) and ``faster_scenarios`` (how many
scenarios the pipeline wins at equal p99 — full mode includes
``us-backbone:lm``, where the win comes from ``solve_mode="sequential"``
windows amortizing per-entry drain-sync/backlog accounting over
heavy bursts in the deep-ledger exact-drain regime).

Timing discipline: every configuration is driven once untimed over the
identical stream first (jit compilation is keyed by data-dependent shapes
— window sizes, deduped closure rows — so only an identical drive warms
every shape), then the better of ``--repeat`` timed drives is kept.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_ROOT))
sys.path.insert(0, str(_ROOT / "src"))

# Each case: scenario, stream shape, and its (δ-in-gaps, B, solve_mode)
# grid.  δ is in units of the mean inter-request gap 1/rate; the bursty
# stream (bursts of ``burst`` requests ~1 ms apart) means a tiny δ already
# captures whole bursts, and the larger-δ rows chart the p99 cost of
# holding windows open longer.
#
# The sub-capacity fluid cases measure per-call dispatch amortization with
# the batched solve (cheap evals — one padded solve per window wins ~2x)
# and carry the stability gate.  The us-backbone:lm case runs the exact
# (ledger) drain past capacity on long heavy-burst streams — the
# deep-committed-backlog regime drain_bench also targets.  There the
# solver is compute-bound and a padded batch's extra per-round candidate
# evaluations cost more than the dispatch they save (closure rows scale
# with batch width on CPU), so its winning rows use
# ``solve_mode="sequential"``: width-1 solves inside one scheduler entry —
# serial plans, amortized drain-sync/accounting — with one batched row
# kept to chart the contrast.
# A 4th grid element, when present, is ``fuse_windows``: with the batched
# solve mode, up to that many queued windows drain per solver start as ONE
# fused multi-window dispatch (``solve_fused``) — cross-arrival batching on
# top of within-window batching.  Fused rows only make sense for
# ``"batched"`` mode (sequential keeps the one-window-per-start contract).
SMOKE_CASES = [
    dict(name="star", arrivals=24, load=0.6, drain="fluid", burst=4,
         grid=[(0.05, 4, "batched"), (0.05, 4, "batched", 2)]),
    dict(name="paper-small", arrivals=24, load=0.6, drain="fluid", burst=4,
         grid=[(0.05, 4, "batched")]),
]
_SMALL_GRID = [(0.05, 2, "batched"), (0.05, 4, "batched"),
               (0.05, 8, "batched"), (0.2, 4, "batched"),
               (1.0, 4, "batched"), (0.05, 4, "batched", 2),
               (0.05, 4, "batched", 4)]
FULL_CASES = [
    dict(name="star", arrivals=40, load=0.6, drain="fluid", burst=4,
         grid=_SMALL_GRID),
    dict(name="paper-small", arrivals=40, load=0.6, drain="fluid", burst=4,
         grid=_SMALL_GRID),
    dict(name="edge-cloud:synthetic", arrivals=32, load=0.6, drain="fluid",
         burst=4, grid=_SMALL_GRID),
    dict(name="us-backbone:lm", arrivals=320, load=1.5, drain="exact",
         burst=8, repeat=3,
         grid=[(0.05, 4, "sequential"), (0.05, 8, "sequential"),
               (0.2, 8, "sequential"), (0.05, 8, "batched"),
               (0.05, 8, "batched", 4)]),
]

P99_EQUAL_TOL = 0.05        # "equal p99": within 5% of the serial loop
DRAIN_BOUNDED_MAX_GROWTH = 1.3   # same absolute stability bar as online_bench
DRAIN_BOUNDED_VS_SERIAL = 1.05   # ... or within 5% of serial on the same drive
EQUIV_ARRIVALS = 12


def _drive(name: str, *, arrivals: int, load: float, drain: str,
           seed: int, burst: int = 4, window_s: float = 0.0,
           max_batch: int = 1, solve_mode: str = "batched",
           fuse_windows: int = 1,
           solver_latency: float | str = "measured") -> tuple:
    """One full streaming session on a fresh scenario; returns (trace, wall)."""
    from repro.scenarios import make_scenario
    from repro.serving.stream import run_stream

    # Fresh scenario per drive: identical rng stream => identical jobs and
    # names across configurations.  The rate comes from a throwaway
    # instance — nominal_rate's calibration advances the name sequence.
    rate = make_scenario(name, seed=0).nominal_rate(load)
    sc = make_scenario(name, seed=0)
    t0 = time.time()
    tr = run_stream(sc, horizon=arrivals / rate, seed=seed,
                    process="bursty", rate=rate, drain=drain,
                    process_params={"burst_size": burst},
                    window_s=window_s, max_batch=max_batch,
                    solve_mode=solve_mode, fuse_windows=fuse_windows,
                    solver_latency=solver_latency)
    return tr, time.time() - t0


def _timed(repeat: int, **kw) -> tuple:
    """Identical warm-up drive, then best-of-``repeat`` timed drives."""
    _drive(**kw)
    best = None
    for _ in range(max(repeat, 1)):
        tr, wall = _drive(**kw)
        if best is None or wall < best[1]:
            best = (tr, wall)
    return best


def _equivalence(name: str, seed: int) -> bool:
    """δ=0, B=1, zero modeled latency == the serial loop, bit-identically
    (modulo the measured solver wall).  Runs on the poisson process — the
    gate is about the window/commit machinery, not the arrival law, and
    poisson guarantees arrivals inside a short horizon."""
    from repro.scenarios import make_scenario
    from repro.serving.online import run_online
    from repro.serving.stream import run_stream

    rate = make_scenario(name, seed=0).nominal_rate(0.6)
    kw = dict(horizon=EQUIV_ARRIVALS / rate, seed=seed, rate=rate)
    serial = run_online(make_scenario(name, seed=0), **kw)
    pipe = run_stream(make_scenario(name, seed=0), window_s=0.0,
                      max_batch=1, solver_latency=0.0, **kw)
    if len(serial.records) != len(pipe.records) or not serial.records:
        return False
    return all(dataclasses.replace(a, solve_s=0.0)
               == dataclasses.replace(b, solve_s=0.0)
               for a, b in zip(serial.records, pipe.records))


def _row(tr, wall: float) -> dict:
    s = tr.summary()
    n = len(tr.requests)
    return {
        "requests": n,
        "windows": s["windows"],
        "mean_window": s["mean_window"],
        "deferred": s["deferred"],
        "shed": s["shed"],
        "wall_s": wall,
        "arr_per_s_wall": n / wall,
        "p50_latency_s": s["p50_latency_s"],
        "p99_latency_s": s["p99_latency_s"],
        "p99_wait_s": s.get("p99_wait_s", 0.0),
        "sustained_arr_s": s["sustained_arr_s"],
        "backlog_growth": s["backlog_growth"],
    }


def _bench_case(case: dict, *, seed: int, repeat: int,
                verbose: bool) -> dict:
    from repro.scenarios import make_scenario

    name, arrivals = case["name"], case["arrivals"]
    load, drain, burst = case["load"], case["drain"], case["burst"]
    repeat = case.get("repeat", repeat)  # noisy cases take best-of-more
    rate = make_scenario(name, seed=0).nominal_rate(load)
    base = dict(name=name, arrivals=arrivals, load=load, drain=drain,
                burst=burst, seed=seed)
    tr, wall = _timed(repeat, **base)
    serial = _row(tr, wall)
    rows = []
    for entry in case["grid"]:
        dmult, B, mode = entry[:3]
        fuse = entry[3] if len(entry) > 3 else 1
        tr, wall = _timed(repeat, window_s=dmult / rate, max_batch=B,
                          solve_mode=mode, fuse_windows=fuse, **base)
        r = _row(tr, wall)
        r.update({
            "window_s": dmult / rate,
            "window_gaps": dmult,
            "max_batch": B,
            "solve_mode": mode,
            "fuse_windows": fuse,
            "speedup": r["arr_per_s_wall"] / serial["arr_per_s_wall"],
            "p99_ratio": r["p99_latency_s"] / serial["p99_latency_s"],
        })
        rows.append(r)
        if verbose:
            print(f"  δ={dmult:4.2f}/rate B={B} {mode[:3]}"
                  f"{f' f={fuse}' if fuse > 1 else '':5s}: "
                  f"{r['arr_per_s_wall']:7.1f} arr/s "
                  f"({r['speedup']:5.2f}x)  p99 {r['p99_latency_s']:8.3f}s "
                  f"(x{r['p99_ratio']:.3f})  win={r['windows']:3d} "
                  f"mean_B={r['mean_window']:.1f}", flush=True)
    equal = [r for r in rows if r["p99_ratio"] <= 1.0 + P99_EQUAL_TOL]
    best = max(equal, key=lambda r: r["speedup"]) if equal else None
    sub_capacity = load < 1.0
    growth_cap = max(DRAIN_BOUNDED_MAX_GROWTH,
                     serial["backlog_growth"] * DRAIN_BOUNDED_VS_SERIAL)
    bounded = all(r["backlog_growth"] <= growth_cap
                  for r in rows) if sub_capacity else True
    out = {
        "scenario": name,
        "arrivals": arrivals,
        "load": load,
        "drain": drain,
        "burst_size": burst,
        "rate_per_s": rate,
        "serial": serial,
        "grid": rows,
        "pipeline_matches_serial": _equivalence(name, seed),
        "drain_bounded": bounded,
        "best_at_equal_p99": best,
        "faster_at_equal_p99": bool(best and best["speedup"] > 1.0),
    }
    if verbose:
        b = best or {"speedup": float("nan"), "p99_ratio": float("nan")}
        print(f"{name:24s} serial {serial['arr_per_s_wall']:7.1f} arr/s  "
              f"best-at-equal-p99 {b['speedup']:5.2f}x "
              f"(p99 x{b['p99_ratio']:.3f})  "
              f"match={out['pipeline_matches_serial']} "
              f"bounded={out['drain_bounded']}", flush=True)
    return out


def run(*, smoke: bool = False, seed: int = 9, repeat: int = 2,
        verbose: bool = True) -> dict:
    cases = SMOKE_CASES if smoke else FULL_CASES
    rows = [_bench_case(case, seed=seed, repeat=repeat, verbose=verbose)
            for case in cases]
    faster = [r["scenario"] for r in rows if r["faster_at_equal_p99"]]
    out = {
        "benchmark": "stream",
        "smoke": smoke,
        "p99_equal_tol": P99_EQUAL_TOL,
        "rows": rows,
        "all_pipeline_match_serial": all(r["pipeline_matches_serial"]
                                         for r in rows),
        "all_bounded": all(r["drain_bounded"] for r in rows),
        "faster_scenarios": faster,
    }
    if verbose:
        print(f"all_pipeline_match_serial={out['all_pipeline_match_serial']} "
              f"all_bounded={out['all_bounded']} "
              f"faster_at_equal_p99={faster}", flush=True)
    return out


def main() -> None:
    from repro.launch import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="2 small scenarios, 1 grid point (the CI gate: "
                         "serial equivalence + bounded backlog)")
    ap.add_argument("--seed", type=int, default=9)
    ap.add_argument("--repeat", type=int, default=2)
    ap.add_argument("--out", default=str(pathlib.Path(__file__).parent
                                         / "BENCH_stream.json"))
    args = ap.parse_args()
    record = run(smoke=args.smoke, seed=args.seed, repeat=args.repeat)
    pathlib.Path(args.out).write_text(json.dumps(record, indent=2))
    print(f"wrote {args.out}")
    if not record["all_pipeline_match_serial"]:
        raise SystemExit("pipeline diverged from the serial loop at "
                         "δ=0, B=1, zero solver latency")
    if not record["all_bounded"]:
        raise SystemExit("windowed pipeline backlog not bounded at "
                         "sub-capacity load")


if __name__ == "__main__":
    main()
