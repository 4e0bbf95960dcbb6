"""End-to-end driver: serve a small model with batched requests, placed by
the paper's routing framework.

    PYTHONPATH=src python examples/serve_routed.py

1. Model a 4-slice serving cluster as the paper's computing network.
2. A batch of inference requests arrives; the RoutedScheduler turns each
   into an InferenceJob (per-layer cost profile) and places it with
   Algorithm 1 — queue-aware, so load spreads and stragglers are avoided.
3. The DecodeEngine actually serves a batch of requests end-to-end
   (prefill + 24 decoded tokens) with a reduced smollm model on CPU.
"""
import sys
import pathlib
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax
import numpy as np

from repro.configs import registry
from repro.core import network as N
from repro.models import model as M
from repro.serving.engine import DecodeEngine
from repro.serving.scheduler import Request, RoutedScheduler, requests_to_jobs


def main():
    # -- 1. cluster model: 4 slices, 2 edge ingress nodes
    G, GB = 1e12, 1e9
    net = N.make_network(
        6,
        [(0, 1, 10 * GB), (1, 2, 40 * GB), (2, 3, 40 * GB), (3, 4, 40 * GB),
         (4, 5, 10 * GB), (1, 3, 40 * GB), (2, 4, 40 * GB)],
        [0, 50 * G, 50 * G, 50 * G, 50 * G, 0])
    sched = RoutedScheduler(net)

    # -- 2. place a mixed batch of requests with the routing framework
    reqs = [Request("olmo_1b", src=0, dst=5, seq_len=2048, name=f"olmo-{i}")
            for i in range(4)]
    reqs += [Request("deepseek_v2_236b", src=0, dst=5, seq_len=2048,
                     name="dsv2-0")]
    plans = sched.schedule_jobs(requests_to_jobs(reqs))
    print("placements (greedy, queue-aware):")
    for p in plans:
        print(f"  prio {p.priority}: {p.job_name:10s} slices {p.nodes_used} "
              f"bound {p.bound_s * 1e3:8.2f} ms")
    used = {n for p in plans for n in p.nodes_used}
    print(f"  -> load spread over {len(used)} slices")

    # a straggling slice: re-plan the *same* batch against the new health
    victim = plans[0].nodes_used[0]
    sched.report_slowdown(victim, 10.0)
    plans2 = sched.replan_last()
    moved = {n for p in plans2 for n in p.nodes_used}
    print(f"  straggler: slice {victim} reported 10x slow -> batch re-planned "
          f"onto {sorted(moved)}")

    # the whole placement is one Plan: serializable, solver-tagged
    plan = sched.last_plan
    print(f"  plan: solver={plan.solver} bound {plan.bound()*1e3:.2f} ms "
          f"({len(str(plan.to_dict()))} chars as JSON)")

    # -- 3. actually serve a batch of requests (reduced model, CPU)
    cfg = registry.smoke_config("smollm_135m")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    engine = DecodeEngine(cfg, params, max_len=64)
    prompts = np.tile(np.arange(8, dtype=np.int32)[None], (4, 1))
    res = engine.generate(prompts, gen_len=24)
    print(f"served batch of 4: prefill {res.prefill_s:.2f}s, "
          f"decode {res.decode_s:.2f}s ({res.tokens_per_s:.1f} tok/s)")
    print(f"sample tokens: {res.tokens[0][:10].tolist()}")
    print("OK")


if __name__ == "__main__":
    main()
