"""Serving driver: routed scheduling + batched decode.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm_135m \
      --requests 4 --gen 16
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import registry
from repro.core import network as N
from repro.models import model as M
from repro.serving.engine import DecodeEngine
from repro.serving.scheduler import Request, RoutedScheduler, requests_to_jobs
from repro.launch import compile_cache


def default_cluster() -> N.ComputeNetwork:
    G, GB = 1e12, 1e9
    return N.make_network(
        6,
        [(0, 1, 10 * GB), (1, 2, 40 * GB), (2, 3, 40 * GB), (3, 4, 40 * GB),
         (4, 5, 10 * GB), (1, 3, 40 * GB), (2, 4, 40 * GB)],
        [0, 50 * G, 50 * G, 50 * G, 50 * G, 0])


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_135m")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--method", default="greedy",
                    help="routing solver (greedy|lazy|sa|exact|registered)")
    args = ap.parse_args()

    sched = RoutedScheduler(default_cluster(), method=args.method)
    plans = sched.schedule_jobs(requests_to_jobs([
        Request(args.arch, src=0, dst=5, seq_len=2048, name=f"req{i}")
        for i in range(args.requests)]))
    for p in plans:
        print(f"[serve] prio {p.priority} {p.job_name}: slices "
              f"{p.nodes_used} bound {p.bound_s*1e3:.2f} ms")
    print(f"[serve] plan: solver={sched.last_plan.solver} "
          f"makespan bound {sched.last_plan.bound()*1e3:.2f} ms")

    cfg = registry.smoke_config(args.arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    engine = DecodeEngine(cfg, params,
                          max_len=args.prompt_len + args.gen + 8)
    prompts = np.tile(np.arange(args.prompt_len, dtype=np.int32)[None],
                      (args.requests, 1))
    extra = {}
    if cfg.family == "encdec":
        from repro.models import encdec
        import jax.numpy as jnp
        frames = jnp.zeros((args.requests, cfg.num_frames, cfg.d_model),
                           cfg.dtype)
        extra["enc_out"] = encdec.encode(cfg, params, frames, remat=False)
    res = engine.generate(prompts, gen_len=args.gen, extra_batch=extra)
    print(f"[serve] {args.requests} requests x {args.gen} tokens: "
          f"{res.tokens_per_s:.1f} tok/s (decode {res.decode_s:.2f}s)")


if __name__ == "__main__":
    main()
