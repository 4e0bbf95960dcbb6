"""Closure-pipeline benchmark: oracle vs Pallas kernels vs closure reuse.

The routing hot-spot is the batched ``[L+1, V, V]`` min-plus transfer
closure.  This benchmark measures, per (V, L):

  * ``oracle_s``        — pure-jnp broadcast closure of the full stack,
  * ``pallas_2d_s``     — the seed's best kernel path: one 2-D Pallas
                          closure per layer slice (a Python loop over L+1),
  * ``pallas_batched_s``— the batched Pallas kernel (leading batch grid
                          dimension, one call for the whole stack),

and, on the paper's small-topology instance:

  * greedy wall-clock with and without round-level closure reuse
    (``share_closures=True`` vs the seed's rebuild-per-call behavior) plus
    the host-level closure-build count of the reuse path,
  * greedy/lazy bounds on the quickstart instance, recorded so the perf
    trajectory carries its own bit-identity check against the seed solver.

Writes ``BENCH_closure.json`` next to this file (or ``--out``).  ``--smoke``
runs tiny shapes with the kernels forced on (interpret mode on CPU) — the CI
regression gate.  Full sizes are sized for real accelerators; on CPU the
interpret-mode kernel paths are semantic-only and slow.

    PYTHONPATH=src python benchmarks/closure_bench.py --smoke
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_ROOT))          # the benchmarks package itself
sys.path.insert(0, str(_ROOT / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

# Pre-change quickstart reference (shared with online_bench's
# static-identity gate): see benchmarks/common.py.
from benchmarks.common import QUICKSTART_BOUNDS, QUICKSTART_ORDER


# v5e roofline constants (same convention as kernel_bench.py): the (min,+)
# contraction is VPU work; the broadcast oracle materializes the [V, V, V]
# intermediate and is HBM-bound, the tiled kernel keeps it in VMEM and is
# compute-bound.
VPU_OPS = 4e12
HBM_BW = 819e9


def _roofline(v: int, layers: int) -> dict:
    b = layers + 1
    squarings = max(1, (v - 1).bit_length())
    ops_total = squarings * b * 2 * v ** 3
    kernel_bytes = squarings * b * 3 * v * v * 4
    oracle_bytes = squarings * b * (v ** 3 + 3 * v * v) * 4
    kernel_s = max(ops_total / VPU_OPS, kernel_bytes / HBM_BW)
    oracle_s = max(ops_total / VPU_OPS, oracle_bytes / HBM_BW)
    return dict(tpu_projected_oracle_s=oracle_s,
                tpu_projected_kernel_s=kernel_s,
                tpu_projected_speedup=oracle_s / kernel_s)


def _rand_stack(v: int, layers: int, seed: int = 0) -> jax.Array:
    """INF-sparse random [L+1, V, V] edge-weight stack."""
    rng = np.random.default_rng(seed)
    w = np.where(rng.random((layers + 1, v, v)) < 0.25,
                 rng.uniform(0.1, 5.0, (layers + 1, v, v)), 1e30)
    return jnp.asarray(w, jnp.float32)


def _time(fn, repeat: int = 3) -> float:
    fn()  # warm (jit/trace)
    t0 = time.perf_counter()
    for _ in range(repeat):
        fn()
    return (time.perf_counter() - t0) / repeat


def bench_kernels(sizes, layer_counts, *, force_pallas: bool,
                  repeat: int, verbose: bool) -> list[dict]:
    rows = []
    use_pallas = True if force_pallas else None
    for v in sizes:
        for L in layer_counts:
            w = _rand_stack(v, L)
            # minplus_closure is already jitted (static use_pallas).
            oracle_s = _time(
                lambda: ops.minplus_closure(w, use_pallas=False)
                .block_until_ready(), repeat)

            def per_slice():
                out = [ops.minplus_closure(w[l], use_pallas=use_pallas)
                       for l in range(L + 1)]
                jax.block_until_ready(out)
            pallas_2d_s = _time(per_slice, repeat)

            pallas_batched_s = _time(
                lambda: ops.minplus_closure(w, use_pallas=use_pallas)
                .block_until_ready(), repeat)

            row = dict(
                V=v, L=L,
                dispatch=ops.minplus_dispatch((L + 1, v, v),
                                              use_pallas=use_pallas),
                oracle_s=oracle_s, pallas_2d_s=pallas_2d_s,
                pallas_batched_s=pallas_batched_s,
                batched_speedup_vs_oracle=oracle_s / pallas_batched_s,
                batched_speedup_vs_2d=pallas_2d_s / pallas_batched_s,
                **_roofline(v, L),
            )
            rows.append(row)
            if verbose:
                print(f"  V={v:4d} L={L:3d} [{row['dispatch']:14s}] "
                      f"oracle {oracle_s*1e3:9.2f} ms  "
                      f"2d {pallas_2d_s*1e3:9.2f} ms  "
                      f"batched {pallas_batched_s*1e3:9.2f} ms")
    return rows


def bench_greedy_reuse(*, repeat: int, verbose: bool) -> dict:
    from repro.core import greedy, jobs as J, network as N, shortest_path as SP
    from benchmarks.common import paper_jobs_small

    net, _ = N.small_topology(capacity_scale=1e-3)
    batch = J.batch_jobs(paper_jobs_small(seed=0))
    J_ = batch.num_jobs

    reuse_s = _time(lambda: greedy.greedy_route(net, batch), repeat)
    rebuild_s = _time(
        lambda: greedy.greedy_route(net, batch, share_closures=False), repeat)

    SP.reset_closure_build_count()
    plan = greedy.greedy_route(net, batch)
    builds = SP.closure_build_count()
    lazy = greedy.greedy_route(net, batch, lazy=True)

    rec = dict(
        num_jobs=J_,
        greedy_reuse_s=reuse_s,
        greedy_rebuild_s=rebuild_s,
        reuse_speedup=rebuild_s / reuse_s,
        closure_builds_reuse=builds,
        lazy_n_routings=int(lazy.meta["n_routings"]),
        greedy_bounds=plan.bounds.tolist(),
        greedy_order=plan.order.tolist(),
        lazy_bounds=lazy.bounds.tolist(),
        bounds_match_seed=bool(
            plan.bounds.tolist() == QUICKSTART_BOUNDS
            and lazy.bounds.tolist() == QUICKSTART_BOUNDS
            and plan.order.tolist() == QUICKSTART_ORDER),
    )
    if verbose:
        print(f"  greedy J={J_}: reuse {reuse_s*1e3:.1f} ms  "
              f"rebuild {rebuild_s*1e3:.1f} ms  "
              f"(x{rec['reuse_speedup']:.2f}, {builds} closure builds)  "
              f"seed-bit-identical={rec['bounds_match_seed']}")
    return rec


def main(argv=None) -> int:
    from repro.launch import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, kernels forced on (CI gate)")
    ap.add_argument("--sizes", type=int, nargs="+", default=None)
    ap.add_argument("--layers", type=int, nargs="+", default=None)
    ap.add_argument("--force-pallas", action="store_true",
                    help="route every kernel row through Pallas even below "
                         "the dispatch threshold (CPU-record mode)")
    ap.add_argument("--repeat", type=int, default=3)
    ap.add_argument("--out", type=pathlib.Path,
                    default=pathlib.Path(__file__).parent / "BENCH_closure.json")
    args = ap.parse_args(argv)

    if args.smoke:
        sizes = args.sizes or [16, 32]
        layer_counts = args.layers or [2]
        force_pallas = True   # tiny shapes would dispatch to the oracle
    else:
        sizes = args.sizes or [64, 256, 512]
        layer_counts = args.layers or [8, 32]
        force_pallas = args.force_pallas

    print(f"closure bench (backend={jax.default_backend()}, "
          f"smoke={args.smoke})")
    kernel_rows = bench_kernels(sizes, layer_counts,
                                force_pallas=force_pallas,
                                repeat=args.repeat, verbose=True)
    greedy_rec = bench_greedy_reuse(repeat=args.repeat, verbose=True)

    record = dict(
        schema=1,
        backend=jax.default_backend(),
        smoke=bool(args.smoke),
        pallas_min_dim=ops._PALLAS_MIN_DIM,
        kernels=kernel_rows,
        greedy=greedy_rec,
        quickstart_reference=dict(bounds=QUICKSTART_BOUNDS,
                                  order=QUICKSTART_ORDER),
    )
    args.out.write_text(json.dumps(record, indent=2) + "\n")
    print(f"wrote {args.out}")
    if not greedy_rec["bounds_match_seed"]:
        print("ERROR: greedy/lazy bounds diverged from the seed solver",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
