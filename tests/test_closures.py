"""Closure-reuse pipeline: build counting, batched dispatch, dedupe,
extract_paths vectorization parity, lazy-greedy device-side bounds, and
bit-identity of the reuse-enabled solvers vs the seed solver."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (greedy, jobs as J, network as N, routing, solvers,
                        telemetry)
from repro.core import shortest_path as SP
from repro.kernels import ops
from util import random_instance

# Pre-change reference captured from the seed solver on the quickstart
# instance (examples/quickstart.py: small_topology(1e-3), 2 VGG19 +
# 6 ResNet34, rng(0)).  The closure-reuse pipeline must reproduce these
# bit-for-bit.
QUICKSTART_BOUNDS = [
    0.9737289547920227, 2.1123697757720947, 0.7822328209877014,
    0.17777971923351288, 0.17777971923351288, 0.334226131439209,
    0.25363287329673767, 0.5179324150085449,
]
QUICKSTART_ORDER = [3, 4, 6, 5, 7, 2, 0, 1]


def _quickstart():
    from repro.configs import registry
    net, _ = N.small_topology(capacity_scale=1e-3)
    rng = np.random.default_rng(0)
    jobs = []
    for i, kind in enumerate(["vgg19"] * 2 + ["resnet34"] * 6):
        src, dst = rng.choice(5, size=2, replace=False)
        jobs.append(registry.get(kind).make_job(f"{kind}-{i}",
                                                int(src), int(dst)))
    return net, J.batch_jobs(jobs)


# ---------------------------------------------------------------------------
# Closure artifact + counting
# ---------------------------------------------------------------------------

def test_one_closure_build_per_greedy_round():
    """A reference greedy round = exactly one closure build (routing +
    commit share the round's stack; the seed rebuilt it J+2 times per
    round).  The fused solver does its closure work inside the device
    program, so the host-level counter stays at zero."""
    rng = np.random.default_rng(0)
    net, jobs = random_instance(rng, num_jobs=5)
    batch = J.batch_jobs(jobs)
    n0 = telemetry.counter("closure_builds")
    greedy.greedy_route_ref(net, batch)
    assert telemetry.counter("closure_builds") - n0 == batch.num_jobs
    n0 = telemetry.counter("closure_builds")
    greedy.greedy_route(net, batch)
    assert telemetry.counter("closure_builds") == n0  # fused: in-program


def test_lazy_one_closure_build_per_round():
    rng = np.random.default_rng(1)
    net, jobs = random_instance(rng, num_jobs=5)
    batch = J.batch_jobs(jobs)
    n0 = telemetry.counter("closure_builds")
    greedy.greedy_route(net, batch, lazy=True)
    assert telemetry.counter("closure_builds") - n0 == batch.num_jobs


def test_solver_meta_reports_closure_builds():
    rng = np.random.default_rng(2)
    net, jobs = random_instance(rng, num_jobs=4)
    batch = J.batch_jobs(jobs)
    plan = solvers.solve(net, batch, method="greedy_ref")
    assert plan.meta["closure_builds"] == batch.num_jobs
    # fused greedy: zero host builds, one dispatch, honest meta
    fused = solvers.solve(net, batch, method="greedy")
    assert fused.meta["closure_builds"] == 0
    assert fused.meta["fused"] is True
    assert fused.meta["dispatches"] == 1
    assert fused.meta["rounds_per_dispatch"] == batch.num_jobs


def test_batch_closures_dedupe_identical_data():
    """Jobs sharing a data-size vector dedupe to one closure computation."""
    net = N.make_network(4, [(0, 1, 2.0), (1, 2, 3.0), (2, 3, 1.0)],
                         [1.0, 2.0, 0.0, 1.5])
    base = J.synthetic_job("a", 0, 3, num_layers=3, seed=0)
    twin = J.InferenceJob("b", 1, 2, base.comp.copy(), base.data.copy())
    other = J.synthetic_job("c", 0, 2, num_layers=3, seed=5)
    batch = J.batch_jobs([base, twin, other])
    cl = SP.build_closures_batch(net, batch)
    assert cl.t.shape == (3, batch.max_layers + 1, 4, 4)
    # w is dropped from batch stacks (cheap to recompute; avoids a J-fold
    # gather) and consumers rebuild it on demand
    assert cl.w is None and cl.job(0).w is None
    # identical data rows -> identical gathered closures
    np.testing.assert_array_equal(np.asarray(cl.t[0]), np.asarray(cl.t[1]))
    # and they match the per-job builder
    single = SP.closures_for(net, batch.data[0])
    np.testing.assert_array_equal(np.asarray(cl.t[0]), np.asarray(single.t))


def test_transfer_closure_stack_dispatches_to_batched_kernel():
    """[L+1, V, V] stacks with V >= the Pallas threshold take the batched
    kernel path (dispatch introspection — acceptance criterion)."""
    import jax
    lmax = 8
    v = 256
    assert ops.minplus_dispatch((lmax + 1, v, v)) == "pallas_batched"
    # trace a real transfer_closure at that size (no execution) and assert
    # its squaring loop reaches the Pallas kernel
    net = N.make_network(v, [(i, (i + 1) % v, 1.0) for i in range(v)],
                         np.ones(v))
    data = jnp.ones((lmax + 1,), jnp.float32)
    traced = jax.make_jaxpr(SP.transfer_closure)(net, data)
    assert traced.out_avals[0].shape == (lmax + 1, v, v)
    assert "pallas_call" in str(traced)
    # and the batched path is numerically right where it is cheap to run
    rng = np.random.default_rng(0)
    w = jnp.asarray(np.where(rng.random((3, 30, 30)) < 0.4,
                             rng.uniform(0.1, 5, (3, 30, 30)),
                             1e30), jnp.float32)
    from repro.kernels import ref
    np.testing.assert_allclose(
        np.asarray(ops.minplus_closure(w, use_pallas=True)),
        np.asarray(ref.minplus_closure_ref(w)), rtol=1e-6)


def test_routing_accepts_prebuilt_closures():
    """route/cost/commit with an explicit Closures == the internal build."""
    rng = np.random.default_rng(3)
    net, jobs = random_instance(rng, num_jobs=1, with_queues=True)
    job = jobs[0]
    comp, data = jnp.asarray(job.comp), jnp.asarray(job.data)
    args = (comp, data, job.src, job.dst, job.num_layers)
    cl = SP.build_closures(net, data)
    r0 = routing.route_single(net, *args)
    r1 = routing.route_single(net, *args, closures=cl)
    # tolerances: the standalone closure build compiles separately from the
    # fused in-jit one, so XLA fusion (FMA) may differ in the last ulp
    np.testing.assert_array_equal(np.asarray(r0.assign), np.asarray(r1.assign))
    np.testing.assert_allclose(float(r0.cost), float(r1.cost), rtol=1e-6)
    c0 = routing.cost_given_assignment(net, *args, r0.assign)
    c1 = routing.cost_given_assignment(net, *args, r0.assign, closures=cl)
    np.testing.assert_allclose(float(c0), float(c1), rtol=1e-6)
    n0 = routing.commit_assignment(net, *args, r0.assign)
    n1 = routing.commit_assignment(net, *args, r0.assign, closures=cl)
    np.testing.assert_allclose(np.asarray(n0.q_link), np.asarray(n1.q_link),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(n0.q_node), np.asarray(n1.q_node),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# extract_paths vectorization parity
# ---------------------------------------------------------------------------

def test_extract_paths_matches_host_reference():
    """Vectorized (vmapped reconstruct_path, one device_get) extract_paths
    == the seed's per-hop host loop."""
    checked = 0
    for seed in range(12):
        rng = np.random.default_rng(seed)
        net, jobs = random_instance(rng, num_jobs=1, with_queues=(seed % 2 == 0))
        job = jobs[0]
        args = (jnp.asarray(job.comp), jnp.asarray(job.data), job.src,
                job.dst, job.num_layers)
        r = routing.route_single(net, *args)
        if float(r.cost) >= 1e29:
            continue
        new = routing.extract_paths(net, *args, r.assign)
        old = routing.extract_paths_ref(net, *args, r.assign)
        assert new == old
        checked += 1
    assert checked >= 5


# ---------------------------------------------------------------------------
# Lazy greedy: device-side cached bounds
# ---------------------------------------------------------------------------

def test_lazy_matches_eager_order_and_routing_budget():
    """Lazy greedy orders jobs exactly like eager Algorithm 1 on seeded
    instances and performs at most J^2 routings."""
    for seed in range(3):
        rng = np.random.default_rng(seed + 40)
        net, jobs = random_instance(rng, num_jobs=6)
        batch = J.batch_jobs(jobs)
        eager = greedy.greedy_route(net, batch)
        lazy = greedy.greedy_route(net, batch, lazy=True)
        assert lazy.meta["n_routings"] <= batch.num_jobs ** 2
        np.testing.assert_array_equal(lazy.order, eager.order)
        np.testing.assert_allclose(lazy.bounds, eager.bounds, rtol=1e-6)


@pytest.mark.parametrize("lazy", [False, True])
def test_unroutable_job_never_double_commits(lazy):
    """A job whose cost clips to the INF sentinel must not tie with (and,
    at a lower index, beat) the routed-job mask in the argmin selection —
    that double-committed a routed job and broke the priority permutation."""
    # job0 feasible (lower index), job1's destination unreachable; data
    # sizes >= 1 so the stranded bound clips to exactly the INF sentinel
    # (data * INF-invrate >= INF), reproducing the tie
    net = N.make_network(4, [(0, 1, 2.0), (1, 2, 2.0)],
                         [0.0, 1.0, 1.0, 1.0])  # node 3: no links at all
    j0 = J.InferenceJob("ok", 0, 2, np.array([1.0], np.float32),
                        np.array([2.0, 2.0], np.float32))
    j1 = J.InferenceJob("stranded", 0, 3, np.array([1.0], np.float32),
                        np.array([2.0, 2.0], np.float32))
    batch = J.batch_jobs([j0, j1])
    plan = greedy.greedy_route(net, batch, lazy=lazy)  # must not raise
    assert sorted(plan.order.tolist()) == [0, 1]
    assert plan.order[0] == 0                 # feasible job routed first
    assert plan.bounds[1] >= 1e29             # stranded job keeps INF bound


# ---------------------------------------------------------------------------
# Bit-identity vs the seed solver (acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lazy", [False, True])
def test_quickstart_bounds_bit_identical_to_seed(lazy):
    net, batch = _quickstart()
    plan = greedy.greedy_route(net, batch, lazy=lazy)
    assert plan.bounds.tolist() == QUICKSTART_BOUNDS
    assert plan.order.tolist() == QUICKSTART_ORDER
