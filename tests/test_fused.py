"""Fused single-dispatch greedy solver: bit-identity vs the host-loop
reference (``greedy_route_ref``) across a seeded scenario catalog, honest
dispatch accounting, cross-arrival multi-window parity, scheduler-level
lockstep, and warmup purity."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import greedy, jobs as J, network as N, solvers, telemetry
from repro.core import schedule, shortest_path as SP
from repro.scenarios import make_scenario
from repro.serving.online import OnlineScheduler
from repro.serving.scheduler import RoutedScheduler
from repro.serving.stream import StreamConfig, StreamingPipeline
from util import random_instance


def _assert_plans_bitwise(fused, ref, *, paths=False):
    assert fused.order.tolist() == ref.order.tolist()
    np.testing.assert_array_equal(np.asarray(fused.assign),
                                  np.asarray(ref.assign))
    assert np.asarray(fused.bounds).tolist() == np.asarray(ref.bounds).tolist()
    np.testing.assert_array_equal(np.asarray(fused.net.q_node),
                                  np.asarray(ref.net.q_node))
    np.testing.assert_array_equal(np.asarray(fused.net.q_link),
                                  np.asarray(ref.net.q_link))
    if paths:
        assert fused.paths == ref.paths


# ---------------------------------------------------------------------------
# Scenario-catalog bit-identity (the CI parity gate's test-suite twin)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed,num_jobs,with_queues", [
    (0, 5, False), (1, 5, True), (2, 7, True),   # 7: odd J exercises pow2 pad
    (3, 3, False), (4, 8, True), (5, 1, True),
])
def test_fused_bit_identical_to_ref(seed, num_jobs, with_queues):
    rng = np.random.default_rng(seed)
    net, jobs = random_instance(rng, num_jobs=num_jobs,
                                with_queues=with_queues)
    batch = J.batch_jobs(jobs)
    fused = greedy.greedy_route(net, batch)
    ref = greedy.greedy_route_ref(net, batch)
    _assert_plans_bitwise(fused, ref)


@pytest.mark.parametrize("with_queues", [False, True])
def test_fused_extract_paths_matches_ref(with_queues):
    """The post-pass path extraction replays the reference's per-round
    extraction bit-for-bit — including at queued states, where an
    FMA-contracted edge weight would flip equal-cost hop ties."""
    rng = np.random.default_rng(10 + with_queues)
    net, jobs = random_instance(rng, num_jobs=6, with_queues=with_queues)
    batch = J.batch_jobs(jobs)
    fused = greedy.greedy_route(net, batch, extract_paths=True)
    ref = greedy.greedy_route_ref(net, batch, extract_paths=True)
    _assert_plans_bitwise(fused, ref, paths=True)
    assert set(fused.paths) == set(range(batch.num_jobs))


def test_fused_extract_paths_matches_ref_on_a_fat_tree():
    """A k=4 fat-tree (V=36) with a 4-job paper window: uniform links give
    every pair of pods four equal-cost paths, so hop ties are everywhere;
    the fused solve still equals the reference bit for bit."""
    sc = make_scenario("fat-tree:paper", seed=0, k=4)
    net = sc.topology.view()
    jobs = sc.sample_jobs(np.random.default_rng(15), 4)
    batch = J.batch_jobs(jobs, pad_to=sc.max_layers)
    fused = greedy.greedy_route(net, batch, extract_paths=True)
    ref = greedy.greedy_route_ref(net, batch, extract_paths=True)
    _assert_plans_bitwise(fused, ref, paths=True)
    hops = [len(h) for p in fused.paths.values() for h in p]
    assert max(hops) <= 6


@pytest.mark.parametrize("scenario,kw", [
    ("us-backbone:paper", {}), ("fat-tree:paper", {"k": 4})],
    ids=["usnet", "fat-tree-k4"])
def test_fused_paths_are_the_hops_the_solve_charged(scenario, kw):
    """Replaying every round's ``plan.paths`` onto the pre-solve link
    queues — each hop += its layer's data, in round order, in float32 —
    reproduces the solve's committed ``q_link`` bit for bit: the paths
    the plan carries are the ones the queues were charged for.  Solved
    at a queued state (a first window already committed)."""
    sc = make_scenario(scenario, seed=0, **kw)
    rng = np.random.default_rng(16)
    first = J.batch_jobs(sc.sample_jobs(rng, 4), pad_to=sc.max_layers)
    net = greedy.greedy_route(sc.topology.view(), first).net
    batch = J.batch_jobs(sc.sample_jobs(rng, 6), pad_to=sc.max_layers)
    plan = greedy.greedy_route(net, batch, extract_paths=True)
    q_link = np.array(net.q_link, np.float32)
    assert q_link.any()
    data = np.asarray(batch.data, np.float32)
    for j in plan.order.tolist():
        for l, hops in enumerate(plan.paths[j]):
            for u, v in hops:
                q_link[u, v] += data[j, l]
    np.testing.assert_array_equal(q_link, np.asarray(plan.net.q_link))


def test_fused_dedupe_rows_bit_identical():
    """Duplicate data rows (the dedupe fast path) keep bit-identity."""
    rng = np.random.default_rng(20)
    net, jobs = random_instance(rng, num_jobs=3, with_queues=True)
    base = jobs[0]
    twins = [dataclasses.replace(base, name=f"twin{i}", src=int(s), dst=int(d))
             if dataclasses.is_dataclass(base) else base
             for i, (s, d) in enumerate([(1, 4), (2, 5)])]
    if not dataclasses.is_dataclass(base):  # plain class: rebuild by hand
        twins = [J.InferenceJob(f"twin{i}", int(s), int(d),
                                base.comp.copy(), base.data.copy())
                 for i, (s, d) in enumerate([(1, 4), (2, 5)])]
    batch = J.batch_jobs(jobs + twins)
    dp = SP.dedupe_plan(batch)
    assert dp.uniq.shape[0] < batch.num_jobs  # dedupe actually engaged
    _assert_plans_bitwise(greedy.greedy_route(net, batch),
                          greedy.greedy_route_ref(net, batch))


def test_fused_unroutable_inf_tie():
    """A stranded job's INF-clipped cost must not tie into the routed-job
    mask inside the fused scan (same guard as the host loop)."""
    net = N.make_network(4, [(0, 1, 2.0), (1, 2, 2.0)],
                         [0.0, 1.0, 1.0, 1.0])  # node 3 unreachable
    j0 = J.InferenceJob("ok", 0, 2, np.array([1.0], np.float32),
                        np.array([2.0, 2.0], np.float32))
    j1 = J.InferenceJob("stranded", 0, 3, np.array([1.0], np.float32),
                        np.array([2.0, 2.0], np.float32))
    batch = J.batch_jobs([j0, j1])
    fused = greedy.greedy_route(net, batch)
    ref = greedy.greedy_route_ref(net, batch)
    _assert_plans_bitwise(fused, ref)
    assert fused.order[0] == 0
    assert fused.bounds[1] >= 1e29


# ---------------------------------------------------------------------------
# Honest dispatch accounting
# ---------------------------------------------------------------------------

def test_fused_solve_is_one_dispatch():
    rng = np.random.default_rng(30)
    net, jobs = random_instance(rng, num_jobs=8)  # 8 = pow2: exact meta
    batch = J.batch_jobs(jobs)
    greedy.greedy_route(net, batch)     # compile warmup, outside the guard
    builds0 = SP.closure_build_count()
    n0 = telemetry.counter("fused_dispatches")
    # transfer_guard("disallow") is the runtime complement of lint rule
    # RL003: any *implicit* host<->device transfer in the warm solve path
    # (all staging must be explicit jax.device_put) fails loudly here, not
    # just via the dispatch counter.
    with jax.transfer_guard("disallow"):
        plan = greedy.greedy_route(net, batch)
    assert telemetry.counter("fused_dispatches") - n0 == 1
    assert SP.closure_build_count() == builds0
    assert plan.meta["fused"] is True
    assert plan.meta["dispatches"] == 1
    assert plan.meta["rounds_per_dispatch"] == batch.num_jobs
    assert plan.meta["windows_per_dispatch"] == 1
    # a second solve at the same shapes must not recompile
    with jax.transfer_guard("disallow"):
        greedy.greedy_route(net, batch)
    assert telemetry.counter("fused_dispatches") - n0 == 2


# ---------------------------------------------------------------------------
# Cross-arrival multi-window parity
# ---------------------------------------------------------------------------

def test_multi_window_matches_sequential_fused():
    """W ragged windows in one multi-window dispatch == W sequential fused
    solves threading committed queues, bit-for-bit."""
    rng = np.random.default_rng(40)
    net, jobs = random_instance(rng, num_jobs=12, with_queues=True)
    sizes = (5, 3, 4)
    batches, off = [], 0
    for n in sizes:
        batches.append(J.batch_jobs(jobs[off:off + n],
                                    pad_to=max(j.num_layers
                                               for j in jobs)))
        off += n
    greedy.greedy_route_windows(net, batches, extract_paths=True)  # warmup
    n0 = telemetry.counter("fused_dispatches")
    # warm multi-window solve must also be implicit-transfer-free (RL003's
    # runtime complement) — ragged windows are padded/staged via device_put
    with jax.transfer_guard("disallow"):
        fused = greedy.greedy_route_windows(net, batches, extract_paths=True)
    assert telemetry.counter("fused_dispatches") - n0 == 1
    cur, seq = net, []
    for b in batches:
        p = greedy.greedy_route(cur, b, extract_paths=True)
        seq.append(p)
        cur = p.net
    for pf, ps in zip(fused, seq):
        _assert_plans_bitwise(pf, ps, paths=True)
        assert pf.meta["windows_per_dispatch"] == len(sizes)


def test_solve_fused_entrypoint_meta():
    rng = np.random.default_rng(41)
    net, jobs = random_instance(rng, num_jobs=6)
    lmax = max(j.num_layers for j in jobs)
    batches = [J.batch_jobs(jobs[:4], pad_to=lmax),
               J.batch_jobs(jobs[4:], pad_to=lmax)]
    plans = solvers.solve_fused(net, batches)
    assert len(plans) == 2
    total_share = sum(p.meta["solve_share_s"] for p in plans)
    for p in plans:
        assert p.meta["fused"] is True
        assert p.meta["solve_share_s"] <= p.meta["solve_s"]
    assert total_share == pytest.approx(plans[0].meta["solve_s"], rel=1e-6)


# ---------------------------------------------------------------------------
# Scheduler-level lockstep
# ---------------------------------------------------------------------------

def _run_online(sc, method, n_windows=3, per=4):
    rng = np.random.default_rng(9)
    s = OnlineScheduler(sc.topology, drain="exact", sim_engine="indexed",
                        track_commits=True, method=method)
    t = 0.0
    for _ in range(n_windows):
        t += 0.05
        s.submit_window(t, sc.sample_jobs(rng, per), pad_to=sc.max_layers)
    s.finish()
    return s, s.replay_ground_truth()


def test_online_fused_reproduces_serial_trace():
    """Exact-mode online run with the fused solver == the greedy_ref run:
    every recorded latency, backlog, completion and replayed ground truth
    (compares values, not names — the scenario job-name counter differs
    between runs)."""
    sc = make_scenario("paper-small", seed=0)
    (sf, gf), (sr, gr) = (_run_online(sc, "greedy"),
                          _run_online(sc, "greedy_ref"))
    for x, y in zip(sf.trace.records, sr.trace.records):
        assert x.latencies == y.latencies
        assert x.backlog_before == y.backlog_before
        assert x.backlog_after == y.backlog_after
    assert (list(sf.trace.completions.values())
            == list(sr.trace.completions.values()))
    assert list(gf.values()) == list(gr.values())


def _scheduler_view(s):
    """What a commit leaves in the scheduler: live ledger names, the
    inflight registry, and the replan snapshot (batch, jobs, ledgers)."""
    batch, jobs, _, _, _, pre_ledger, _ = s._last
    names = (lambda led: None if led is None
             else [j.name for j in led.jobs])
    return (names(s.ledger), list(s.inflight_jobs),
            [np.asarray(x).tolist() for x in jax.tree_util.tree_leaves(batch)],
            [j.name for j in jobs], names(pre_ledger))


@pytest.mark.parametrize("drain,epochs,sizes,gap", [
    ("fluid", 3, (4, 3), 0.05),
    ("exact", 3, (4, 3), 0.05),
    # 2,112 commits at half load: past the inflight registry's prune
    # threshold (2,048 entries), so both paths must prune alike.
    ("exact", 33, (32, 32), 16.0),
])
def test_submit_windows_matches_sequential_submits(drain, epochs, sizes, gap):
    """Fused cross-arrival submission vs W sequential submit_window calls.

    Fluid mode is bit-identical.  Exact mode re-materializes queues from
    the ledger between sequential commits while the fused chain threads
    the solver's committed queues mid-dispatch, so recorded *bounds* may
    drift by f32-ulp rounding (~1e-7 relative); committed work — and
    hence completions and replayed ground truth — stays bitwise equal,
    as does the backlog telemetry (read from per-window post-commit
    snapshots).  Both paths leave the same scheduler state behind: live
    ledger, inflight registry and replan snapshot."""
    sc = make_scenario("paper-small", seed=0)
    rng = np.random.default_rng(7)
    epochs_w = [[sc.sample_jobs(rng, n) for n in sizes]
                for _ in range(epochs)]

    def run(mode):
        kw = (dict(track_commits=True, sim_engine="indexed")
              if drain == "exact" else {})
        s = OnlineScheduler(sc.topology, drain=drain, **kw)
        t = 0.0
        for wins in epochs_w:
            t += gap
            if mode == "fused":
                s.submit_windows(t, wins, pad_to=sc.max_layers)
            else:
                for w in wins:
                    s.submit_window(t, w, pad_to=sc.max_layers)
        view = _scheduler_view(s)
        if drain == "exact":
            s.finish()
        return s, view

    (mf, vf), (ms, vs) = run("fused"), run("seq")
    assert vf == vs
    assert len(mf.trace.records) == len(ms.trace.records)
    for x, y in zip(mf.trace.records, ms.trace.records):
        assert x.backlog_before == y.backlog_before
        assert x.backlog_after == y.backlog_after
        if drain == "fluid":
            assert x.latencies == y.latencies
        else:
            np.testing.assert_allclose(np.asarray(x.latencies),
                                       np.asarray(y.latencies),
                                       rtol=1e-5)
    if drain == "exact":
        assert (list(mf.trace.completions.values())
                == list(ms.trace.completions.values()))
        assert (list(mf.replay_ground_truth().values())
                == list(ms.replay_ground_truth().values()))


# ---------------------------------------------------------------------------
# Warmup purity
# ---------------------------------------------------------------------------

def test_warmup_is_pure_and_caches():
    sc = make_scenario("paper-small", seed=0)
    rng = np.random.default_rng(50)
    s = OnlineScheduler(sc.topology, drain="exact", sim_engine="indexed",
                        track_commits=True)
    sample = sc.sample_jobs(rng, 5)
    qn0 = np.asarray(s.state.q_node).copy()
    ql0 = np.asarray(s.state.q_link).copy()
    clock0, ledger0 = s._now, s.ledger
    n_records0 = len(s.trace.records)
    out = s.warmup(sample, pad_to=sc.max_layers, window_counts=(2,))
    assert out["compiles"] >= 1
    assert out["wall_s"] > 0
    np.testing.assert_array_equal(np.asarray(s.state.q_node), qn0)
    np.testing.assert_array_equal(np.asarray(s.state.q_link), ql0)
    assert s._now == clock0 and s.ledger is ledger0
    assert len(s.trace.records) == n_records0
    # warmed shapes: a second warmup compiles nothing
    again = s.warmup(sample, pad_to=sc.max_layers, window_counts=(2,))
    assert again["compiles"] == 0


# ---------------------------------------------------------------------------
# The scheduler's host batch: staged and committed with no fetch
# ---------------------------------------------------------------------------

def _moved(keys, fn):
    before = {k: telemetry.counter(k) for k in keys}
    out = fn()
    return out, {k: telemetry.counter(k) - v for k, v in before.items()}


@pytest.mark.parametrize("size", [1, 4, 3], ids=["one", "full", "ragged"])
@pytest.mark.parametrize("scenario,kw", [
    ("us-backbone:paper", {}), ("fat-tree:paper", {"k": 4})],
    ids=["usnet", "fat-tree-k4"])
def test_host_batch_solves_as_its_device_copy(scenario, kw, size):
    """``greedy_route`` on a host batch and on its device copy gives
    bit-identical order, assign, bounds, paths and post-commit queues, at
    a queued state, for a one-job, a full and a ragged window (3 jobs,
    padded to 4 on the host).  The host batch is staged with no fetch:
    its solve waits once, for the scan's results.  ``job_stages`` reads
    the same stages from either copy."""
    sc = make_scenario(scenario, seed=0, **kw)
    rng = np.random.default_rng(60 + size)
    first = J.batch_jobs(sc.sample_jobs(rng, 4), pad_to=sc.max_layers)
    net = greedy.greedy_route(sc.topology.view(), first).net
    host = J.batch_jobs_host(sc.sample_jobs(rng, size), pad_to=sc.max_layers)
    device = telemetry.to_device(host)
    ref = greedy.greedy_route(net, device, extract_paths=True)
    with jax.transfer_guard("disallow"):
        plan, moved = _moved(
            ("d2h", "batch_fetches"),
            lambda: greedy.greedy_route(net, host, extract_paths=True))
    assert moved == {"d2h": 1, "batch_fetches": 0}
    _assert_plans_bitwise(plan, ref, paths=True)
    assert set(plan.paths) == set(range(size))
    stages, moved = _moved(("d2h", "batch_fetches"), lambda: (
        schedule.job_stages(host, plan.assign, plan.paths)))
    assert moved == {"d2h": 0, "batch_fetches": 0}
    assert stages == schedule.job_stages(device, ref.assign, ref.paths)


@pytest.mark.parametrize("wrapped", [False, True], ids=["plain", "wrapped"])
def test_a_recorded_pipeline_fetches_no_batch(wrapped, monkeypatch):
    """The served path (``StreamingPipeline`` over an exact-drain
    ``OnlineScheduler``) in ragged 3-job windows: the recorder counts no
    ``batch_fetches`` and one wait per batch, also with ``"greedy"``
    re-registered as a plain wrapper, as the benchmark times it."""
    if wrapped:
        fused = solvers.get("greedy")
        monkeypatch.setitem(solvers._REGISTRY, "greedy",
                            lambda net, batch, **opts: fused(net, batch,
                                                             **opts))
    sc = make_scenario("us-backbone:paper", seed=0)
    rng = np.random.default_rng(70)
    pipe = StreamingPipeline(
        OnlineScheduler(sc.topology, method="greedy", drain="exact"),
        StreamConfig(window_s=0.0, max_batch=3, solve_mode="batched",
                     solver_latency=0.0))
    gap = 3 / sc.nominal_rate(0.8)

    def run(t0, n):
        epochs = [(t0 + gap * (i + 1), sc.sample_jobs(rng, 3))
                  for i in range(n)]
        assert not pipe.run(epochs, pad_to=sc.max_layers).shed

    run(0.0, 2)                      # compiles every program the path runs
    telemetry.enable()
    try:
        run(3 * gap, 3)
    finally:
        telemetry.disable()
    rec = telemetry.snapshot()
    batches = sum(s[0] == "sched.submit_window" for s in rec["spans"])
    assert batches == 3
    assert rec["counters"].get("batch_fetches", 0) == 0
    assert rec["counters"]["d2h"] == batches


def _spy_batch_uploads(monkeypatch) -> list:
    """Record the type of each pytree ``telemetry.to_device`` uploads."""
    uploads, to_device = [], telemetry.to_device

    def spy(x):
        uploads.append(type(x))
        return to_device(x)

    monkeypatch.setattr(telemetry, "to_device", spy)
    return uploads


@pytest.mark.parametrize("method", ["greedy_ref", "lazy"])
def test_a_method_that_jits_the_batch_gets_one_upload(method, monkeypatch):
    """The scheduler holds host batches; a method that indexes or jits
    the batch itself gets its device copy through one explicit upload in
    ``solvers.solve``.  The ledger reads the host copy; the one batch
    fetch is the reference solver's own dedupe of its device copy."""
    sc = make_scenario("us-backbone:paper", seed=0)
    seen, solver = [], solvers.get(method)

    def watched(net, batch, **opts):
        seen.append(isinstance(batch.data, jax.Array))
        return solver(net, batch, **opts)

    monkeypatch.setitem(solvers._REGISTRY, method, watched)
    sched = RoutedScheduler(sc.topology, method=method, drain="exact")
    uploads = _spy_batch_uploads(monkeypatch)
    jobs = sc.sample_jobs(np.random.default_rng(80), 3)
    _, moved = _moved(("batch_fetches",), lambda: sched.schedule_jobs(
        jobs, pad_to=sc.max_layers))
    assert seen == [True]
    assert uploads.count(J.JobBatch) == 1
    assert moved == {"batch_fetches": 1}
    assert sched.ledger.jobs and sched.last_plan.paths is not None


def test_a_jitting_method_runs_under_the_transfer_guard(monkeypatch):
    """A registered method that jits the batch it is handed runs from the
    scheduler under ``transfer_guard("disallow")``: the host batch reaches
    it through ``solvers.solve``'s one counted upload, never an implicit
    transfer."""
    sc = make_scenario("us-backbone:paper", seed=0)
    probe = jax.jit(lambda b: b.comp.sum())

    def jit_probe(net, batch, **opts):
        probe(batch)
        return greedy.greedy_route(net, batch, **opts)

    monkeypatch.setitem(solvers._REGISTRY, "jit_probe", jit_probe)
    sched = RoutedScheduler(sc.topology, method="jit_probe")
    rng = np.random.default_rng(90)
    sched.schedule_jobs(sc.sample_jobs(rng, 2), pad_to=sc.max_layers)
    uploads = _spy_batch_uploads(monkeypatch)
    with jax.transfer_guard("disallow"):
        placed, moved = _moved(("batch_fetches",), lambda: (
            sched.schedule_jobs(sc.sample_jobs(rng, 2),
                                pad_to=sc.max_layers)))
    assert len(placed) == 2
    assert uploads.count(J.JobBatch) == 1
    # the fused greedy stages the device copy it was handed: one fetch
    assert moved == {"batch_fetches": 1}
