#!/usr/bin/env python3
"""On-chip smoke run of the served scheduler path and the min-plus kernel.

  python chip_smoke.py

Runs in one process on one TPU chip; every input is generated from a
seed.  Phases, in order — any failure exits non-zero and prints no result:

1. **device** — the default device must be a TPU.  There is no CPU
   fallback: on a machine without one the script fails here.
2. **kernel** — ``ops.minplus_closure(use_pallas=True)`` on an INF-sparse
   ``[8, 256, 256]`` stack (the batched kernel) and one 2-D min-plus
   squaring at V=1408 (a k=16 fat-tree's 1344 nodes padded to the 128
   tile).  Each must equal the ``ref.minplus_matmul_ref`` oracle bit for
   bit on the chip — min over f32 adds is exact in any order — and each
   compiled program must contain the Mosaic kernel (``tpu_custom_call``),
   which interpret mode never emits.
3. **served** — ``run_stream`` on ``us-backbone:lm`` (USNET, V=24, 32-layer
   LM cost profiles) with the exact drain, commit tracking, warm-up and the
   measured solver-latency model, for at least 30 windows of 32 jobs.  It
   requires no ``solver_error`` shed, no compile after warm-up, one
   dispatch per solve, the incremental exact drain's completions equal to
   the full replay's (rtol 1e-9), and the fused greedy solve equal to the
   host-loop reference ``greedy_route_ref`` on order and assignment for 3
   served windows, with bounds within rtol 1e-6.

Earlier lines print compile and solve walls labelled with the device kind:
smoke-run walls, not benchmark metrics.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import functools
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402

SEED = 0
STACK_SHAPE = (8, 256, 256)
SQUARE_V = 1408
# Oracle row block at V=1408: a [128, V, V] f32 intermediate is ~1 GB.
ORACLE_ROWS = 128
SCENARIO = "us-backbone:lm"
LOAD = 0.8
JOBS_PER_ARRIVAL = 32
MIN_WINDOWS = 30
REF_WINDOWS = 3
COMPLETION_RTOL = COMPLETION_ATOL = 1e-9
BOUND_RTOL = 1e-6


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def require_mosaic(compiled, what: str) -> None:
    require("tpu_custom_call" in compiled.as_text(),
            f"{what}: compiled program has no tpu_custom_call (interpret "
            "mode or an oracle path ran instead of the Pallas kernel)")


def device_phase() -> dict:
    devices = jax.devices()
    d = devices[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}
    log(f"device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    require(d.platform == "tpu",
            f"no TPU: the default device is {d.platform!r}")
    return info


def _inf_sparse(rng, shape, density: float) -> np.ndarray:
    """Edge weights in [1, 10) on a ``density`` fraction of pairs, the
    network's INF sentinel elsewhere."""
    from repro.core.network import INF
    w = rng.uniform(1.0, 10.0, shape).astype(np.float32)
    return np.where(rng.random(shape) < density, w, np.float32(INF))


def _compile(fn, *args):
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, time.perf_counter() - t0


def kernel_phase(kind: str) -> None:
    from repro.core.network import INF
    from repro.kernels import ops, ref

    rng = np.random.default_rng(SEED)

    # Closure of a batched stack: every squaring on the batched kernel.
    w = jax.device_put(_inf_sparse(rng, STACK_SHAPE, 0.02))
    closure, t_c = _compile(
        functools.partial(ops.minplus_closure, use_pallas=True), w)
    require_mosaic(closure, f"minplus_closure{list(STACK_SHAPE)}")
    got = np.asarray(closure(w))
    want = np.asarray(ops.minplus_closure(w, use_pallas=False))
    require(np.array_equal(got, want),
            f"minplus_closure{list(STACK_SHAPE)}: Pallas != oracle "
            f"({int((got != want).sum())} entries differ)")
    require((got < INF).sum() > (np.asarray(w) < INF).sum(),
            "closure found no multi-hop path: the check would be vacuous")
    log(f"[{kind}] kernel: closure {list(STACK_SHAPE)} == oracle bit for "
        f"bit; compile {t_c:.3f} s")

    # One 2-D squaring at fat-tree scale on the 2-D kernel.
    v = SQUARE_V
    d = _inf_sparse(rng, (v, v), 0.01)
    np.fill_diagonal(d, 0.0)
    d = jax.device_put(d)
    square, t_s = _compile(
        functools.partial(ops.minplus_matmul, use_pallas=True), d, d)
    require_mosaic(square, f"minplus_matmul[{v}x{v}]")
    t0 = time.perf_counter()
    got = np.asarray(square(d, d))
    t_run = time.perf_counter() - t0
    oracle = jax.jit(ref.minplus_matmul_ref)
    want = np.concatenate([np.asarray(oracle(d[r:r + ORACLE_ROWS], d))
                           for r in range(0, v, ORACLE_ROWS)])
    require(np.array_equal(got, want),
            f"minplus_matmul[{v}x{v}]: Pallas != oracle "
            f"({int((got != want).sum())} entries differ)")
    log(f"[{kind}] kernel: squaring V={v} == oracle bit for bit; compile "
        f"{t_s:.3f} s, first run {t_run:.4f} s")


def served_phase(kind: str) -> None:
    from repro.core import greedy, solvers
    from repro.scenarios import make_scenario
    from repro.serving.stream import run_stream

    # Record every greedy solve the scheduler makes (the registry lets a
    # caller shadow a built-in solver); the served windows are the last
    # len(trace.windows) of them, after the warm-up's solves.
    fused = solvers.get("greedy")
    solves = []

    def recording_greedy(net, batch, **opts):
        plan = fused(net, batch, **opts)
        solves.append((net, batch, opts, plan))
        return plan

    sc = make_scenario(SCENARIO, seed=SEED)
    rate = sc.nominal_rate(LOAD)
    solvers.register("greedy")(recording_greedy)
    try:
        t0 = time.perf_counter()
        trace = run_stream(
            sc, horizon=(MIN_WINDOWS + 10) / rate, seed=SEED,
            process="poisson", rate=rate, batch_size=JOBS_PER_ARRIVAL,
            max_batch=JOBS_PER_ARRIVAL, solver_latency="measured",
            warmup=True, finish=True, drain="exact", track_commits=True)
        wall = time.perf_counter() - t0
    finally:
        solvers.register("greedy")(fused)

    n_win = len(trace.windows)
    require(n_win >= MIN_WINDOWS, f"only {n_win} windows served")
    errors = trace.shed_by_reason().get("solver_error", 0)
    require(errors == 0, f"{errors} requests shed as solver_error")
    require(len(solves) >= n_win, "fewer solves than windows")
    served = solves[-n_win:]
    plans = [s[3] for s in served]
    require(sum(p.num_jobs for p in plans) == len(trace.requests),
            "served solves do not line up with the committed requests")
    require(not trace.compile_walls
            and not any(p.meta.get("jit_compiled") for p in plans),
            f"{len(trace.compile_walls)} served solves compiled after "
            "warm-up")
    require(all(p.meta.get("dispatches") == 1 for p in plans),
            "a served solve took more than one dispatch")

    comps, replay = trace.completions, trace.replay_completions
    require(len(comps) == len(trace.requests)
            and comps.keys() == replay.keys(),
            "exact drain and replay completed different job sets")
    off = [n for n in comps if abs(comps[n] - replay[n])
           > COMPLETION_ATOL + COMPLETION_RTOL * abs(replay[n])]
    require(not off, f"{len(off)} completions differ from the replay")

    bitwise, worst = True, 0.0
    for i in np.linspace(0, n_win - 1, REF_WINDOWS).round().astype(int):
        net, batch, opts, plan = served[i]
        ref_plan = greedy.greedy_route_ref(net, batch, **opts)
        require(np.array_equal(plan.order, ref_plan.order)
                and np.array_equal(plan.assign, ref_plan.assign),
                f"window {i}: fused order/assignment != greedy_route_ref")
        require(np.allclose(plan.bounds, ref_plan.bounds, rtol=BOUND_RTOL,
                            atol=0.0),
                f"window {i}: fused bounds != greedy_route_ref")
        bitwise &= bool(np.array_equal(plan.bounds, ref_plan.bounds)
                        and plan.paths == ref_plan.paths)
        worst = max(worst, float(np.max(np.abs(plan.bounds - ref_plan.bounds)
                                        / np.abs(ref_plan.bounds))))

    walls = np.array([w.solve_wall_s for w in trace.windows])
    log(f"[{kind}] served: {n_win} windows, {len(comps)} jobs, run wall "
        f"{wall:.3f} s; warm solve wall p50 {np.median(walls):.4f} s, max "
        f"{walls.max():.4f} s")
    log(f"[{kind}] served: fused == greedy_route_ref bit for bit "
        f"(bounds and paths) on {REF_WINDOWS} windows: {bitwise}; max "
        f"relative bound difference {worst!r}")


def main() -> None:
    info = device_phase()
    from repro.launch import compile_cache
    log(f"compile cache: {compile_cache.enable()}")
    kernel_phase(info["kind"])
    served_phase(info["kind"])
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
