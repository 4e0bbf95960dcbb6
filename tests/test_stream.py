"""Streaming serving pipeline: window semantics (δ/B), serial equivalence,
decoupled solver stage, and backpressure (defer/shed) accounting."""
import dataclasses
import json

import numpy as np
import pytest

from repro.scenarios import make_scenario
from repro.serving.online import run_online
from repro.serving.stream import (StreamConfig, StreamingPipeline,
                                  StreamTrace, run_stream)


@pytest.fixture(scope="module")
def star():
    return make_scenario("star", seed=0)


def _jobs(sc, n, seed=0):
    return sc.sample_jobs(np.random.default_rng(seed), n)


def _pipe(sc, **cfg):
    return StreamingPipeline(sc.topology, StreamConfig(**cfg))


# -- config validation -------------------------------------------------------

def test_config_validation():
    with pytest.raises(ValueError, match="window_s"):
        StreamConfig(window_s=-1.0)
    with pytest.raises(ValueError, match="max_batch"):
        StreamConfig(max_batch=0)
    with pytest.raises(ValueError, match="policy"):
        StreamConfig(policy="drop")
    with pytest.raises(ValueError, match="max_pending"):
        StreamConfig(max_pending=0)
    with pytest.raises(ValueError, match="solver_latency"):
        StreamConfig(solver_latency="estimated")
    with pytest.raises(ValueError, match="solver_latency"):
        StreamConfig(solver_latency=-0.1)


# -- the correctness gate: δ=0, B=1, zero latency == serial loop -------------

def test_serial_equivalence_bit_identical():
    """With no window, no batching and no modeled solver latency the
    pipeline must reproduce the serial OnlineScheduler trace bit-identically
    (everything except the measured solver wall, which is wall-clock)."""
    # rate from a throwaway instance: nominal_rate's calibration samples 32
    # jobs and advances the scenario's name sequence, so both runs must
    # start from untouched scenarios.
    rate = make_scenario("star", seed=0).nominal_rate(0.5)
    kw = dict(horizon=20 / rate, seed=1, rate=rate)
    serial = run_online(make_scenario("star", seed=0), **kw)
    pipe = run_stream(make_scenario("star", seed=0), window_s=0.0,
                      max_batch=1, solver_latency=0.0, **kw)
    assert len(serial.records) == len(pipe.records) >= 10
    for a, b in zip(serial.records, pipe.records):
        assert dataclasses.replace(a, solve_s=0.0) == \
            dataclasses.replace(b, solve_s=0.0)
    assert serial.events == pipe.events
    # decomposition agrees: zero wait, every window is one request
    assert all(r.wait_s == 0.0 for r in pipe.requests)
    assert all(w.size == 1 for w in pipe.windows)
    assert [r.commit_s for r in pipe.requests] == \
        [r.arrival_s for r in pipe.requests]


def test_serial_equivalence_exact_drain():
    """The gate holds under the exact (ledger) drain too — the pipeline
    changes when plans land, never how the drain accounts for them."""
    rate = make_scenario("paper-small", seed=0).nominal_rate(0.8)
    kw = dict(horizon=8 / rate, seed=2, rate=rate, drain="exact",
              finish=True)
    serial = run_online(make_scenario("paper-small", seed=0), **kw)
    pipe = run_stream(make_scenario("paper-small", seed=0), window_s=0.0,
                      max_batch=1, solver_latency=0.0, **kw)
    for a, b in zip(serial.records, pipe.records):
        assert dataclasses.replace(a, solve_s=0.0) == \
            dataclasses.replace(b, solve_s=0.0)
    assert serial.completions == pipe.completions


# -- window semantics --------------------------------------------------------

def test_window_closes_at_batch_cap(star):
    """B arrivals inside δ close the window early — at the B-th arrival."""
    jobs = _jobs(star, 6)
    stream = [(0.1 * i, [j]) for i, j in enumerate(jobs)]
    tr = _pipe(star, window_s=100.0, max_batch=3).run(
        iter(stream), horizon=1000.0, pad_to=star.max_layers)
    assert [w.size for w in tr.windows] == [3, 3]
    # closed by cap, not by the δ timer: at the 3rd/6th arrival instants
    assert [w.close_s for w in tr.windows] == [0.2, 0.5]
    assert [w.commit_s for w in tr.windows] == [0.2, 0.5]
    assert len(tr.records) == 2  # one ArrivalRecord per window commit


def test_window_flushes_at_delta(star):
    """Fewer than B arrivals: the window flushes δ after it opened."""
    jobs = _jobs(star, 2)
    stream = [(0.0, [jobs[0]]), (0.3, [jobs[1]])]
    tr = _pipe(star, window_s=1.0, max_batch=100).run(
        iter(stream), horizon=1000.0, pad_to=star.max_layers)
    assert [w.size for w in tr.windows] == [2]
    assert tr.windows[0].open_s == 0.0 and tr.windows[0].close_s == 1.0
    # both requests waited for the flush: wait = commit - arrival
    assert [r.wait_s for r in tr.requests] == [1.0, 0.7]


def test_partial_window_flushed_at_horizon_end(star):
    """A window still open when the stream ends flushes at the horizon,
    not after the full δ."""
    jobs = _jobs(star, 2)
    stream = [(0.2, [jobs[0]]), (0.4, [jobs[1]])]
    tr = _pipe(star, window_s=50.0, max_batch=100).run(
        iter(stream), horizon=1.0, pad_to=star.max_layers)
    assert [w.size for w in tr.windows] == [2]
    assert tr.windows[0].close_s == 1.0
    assert all(r.commit_s == 1.0 for r in tr.requests)


def test_empty_windows_skipped(star):
    """Stale flush timers and empty arrival epochs never produce an empty
    solve: every recorded window carries at least one request."""
    jobs = _jobs(star, 2)
    # epoch 1 fills the window to its cap (closing it, leaving the δ=5
    # flush timer stale); epoch 2 is an empty epoch at t=1
    stream = [(0.0, jobs), (1.0, [])]
    tr = _pipe(star, window_s=5.0, max_batch=2).run(
        iter(stream), horizon=100.0, pad_to=star.max_layers)
    assert [w.size for w in tr.windows] == [2]
    assert len(tr.records) == 1


def test_sequential_mode_commits_serial_plans(star):
    """solve_mode='sequential' places a window with width-1 solves in
    window order — bit-identical plans (bounds, latencies, solve total) to
    the serial loop submitting the same jobs one call at a time at the
    same instant."""
    from repro.serving.online import OnlineScheduler

    jobs = _jobs(star, 5)
    seq = OnlineScheduler(star.topology)
    seq.trace = StreamTrace()
    got = seq.submit_window(2.0, jobs, pad_to=star.max_layers,
                            solve_mode="sequential")
    serial = OnlineScheduler(star.topology)
    want = [p for j in jobs
            for p in serial.submit_window(2.0, [j], pad_to=star.max_layers)]
    assert [p.job_name for p in got] == [p.job_name for p in want]
    assert [p.bound_s for p in got] == [p.bound_s for p in want]
    assert [p.assign.tolist() for p in got] == \
        [p.assign.tolist() for p in want]
    # one window record carrying the whole window, solve wall = the sum
    assert len(seq.trace.records) == 1
    rec = seq.trace.records[0]
    assert rec.latencies == tuple(
        x for r in serial.trace.records for x in r.latencies)
    # solve wall is the window total (walls themselves aren't comparable
    # across schedulers — the first run pays jit compilation)
    assert rec.solve_s > 0 and seq.last_solve_s == rec.solve_s
    with pytest.raises(ValueError, match="solve_mode"):
        seq.submit_window(3.0, jobs[:1], solve_mode="fused")
    with pytest.raises(ValueError, match="solve_mode"):
        StreamConfig(solve_mode="fused")


def test_sequential_pipeline_matches_serial_at_b1(star):
    """At B=1 the two solve modes are the same code path — the serial
    equivalence gate holds for either."""
    rate = make_scenario("star", seed=0).nominal_rate(0.5)
    kw = dict(horizon=8 / rate, seed=6, rate=rate, window_s=0.0,
              max_batch=1, solver_latency=0.0)
    a = run_stream(make_scenario("star", seed=0), solve_mode="batched", **kw)
    b = run_stream(make_scenario("star", seed=0),
                   solve_mode="sequential", **kw)
    assert len(a.records) == len(b.records) >= 4
    for ra, rb in zip(a.records, b.records):
        assert dataclasses.replace(ra, solve_s=0.0) == \
            dataclasses.replace(rb, solve_s=0.0)


def test_solver_latency_delays_commits(star):
    """Modeled solver wall-time lands on the simulated clock: commits are
    pushed out by the latency and a busy solver queues the next window."""
    jobs = _jobs(star, 2)
    stream = [(0.0, [jobs[0]]), (0.1, [jobs[1]])]
    tr = _pipe(star, window_s=0.0, max_batch=1, solver_latency=0.5).run(
        iter(stream), horizon=10.0, pad_to=star.max_layers)
    # window 1: solve starts at 0.0, commits at 0.5; window 2 closed at
    # 0.1 but the solver is busy until 0.5 -> commits at 1.0
    assert [w.commit_s for w in tr.windows] == [0.5, 1.0]
    assert [r.wait_s for r in tr.requests] == [0.5, 0.9]
    assert [r.queue_s for r in tr.requests] == pytest.approx([0.0, 0.4])
    # the scheduler's authoritative clock followed the commits
    assert [r.time for r in tr.records] == [0.5, 1.0]


def test_latency_is_wait_plus_service(star):
    """The recorded per-request latency (OnlineTrace.latencies) equals the
    decomposition's wait + service, request by request."""
    rate = star.nominal_rate(0.5)
    tr = run_stream(star, horizon=12 / rate, seed=3, rate=rate,
                    window_s=1.0 / rate, max_batch=4, solver_latency=0.01)
    assert tr.requests
    by_window: dict[int, list] = {}
    for r in tr.requests:
        by_window.setdefault(r.window, []).append(r)
    lat_from_records = np.sort(tr.latencies)
    lat_from_requests = np.sort([r.latency_s for r in tr.requests])
    np.testing.assert_allclose(lat_from_records, lat_from_requests,
                               rtol=1e-12)


# -- backpressure ------------------------------------------------------------

def test_defer_never_reorders_arrivals(star):
    """Deferred arrivals re-enter FIFO at commit instants, ahead of later
    traffic: the committed order is exactly the arrival order."""
    jobs = _jobs(star, 10)
    stream = [(0.1 * i, [j]) for i, j in enumerate(jobs)]
    tr = _pipe(star, window_s=0.0, max_batch=1, solver_latency=0.5,
               max_pending=2, policy="defer").run(
        iter(stream), horizon=1.0, pad_to=star.max_layers)
    assert [r.name for r in tr.requests] == [j.name for j in jobs]
    assert tr.deferred == 8 and not tr.shed
    # deferral is visible in the decomposition: admit > arrival, and the
    # whole deferral wait is charged to the request's latency
    deferred = [r for r in tr.requests if r.admit_s > r.arrival_s]
    assert len(deferred) == 8
    assert all(r.wait_s >= r.admit_s - r.arrival_s for r in deferred)
    # pending buffer never exceeded its bound: commits are serialized, so
    # each commit's window plus spill re-admissions stay within cap
    assert all(w.size <= 2 for w in tr.windows)


def test_shed_policy_accounting(star):
    """policy='shed' drops arrivals beyond the buffer and accounts them:
    shed requests never commit, committed + shed == offered."""
    jobs = _jobs(star, 10)
    stream = [(0.1 * i, [j]) for i, j in enumerate(jobs)]
    tr = _pipe(star, window_s=0.0, max_batch=1, solver_latency=0.5,
               max_pending=2, policy="shed").run(
        iter(stream), horizon=1.0, pad_to=star.max_layers)
    committed = {r.name for r in tr.requests}
    shed = {s["name"] for s in tr.shed}
    assert committed | shed == {j.name for j in jobs}
    assert committed.isdisjoint(shed)
    assert len(shed) == 7 and tr.deferred == 0
    s = tr.summary()
    assert s["shed"] == 7 and s["requests"] == 3


def test_backlog_bounded_under_subcapacity_window(star):
    """Sub-capacity bursty load through a batching window: the drained
    backlog stays bounded (the serial stability property survives
    batching)."""
    rate = star.nominal_rate(0.5)
    tr = run_stream(star, horizon=60 / rate, seed=4, process="bursty",
                    rate=rate, window_s=0.2 / rate, max_batch=4)
    assert len(tr.records) >= 10
    assert tr.backlog_growth() <= 1.3, tr.summary()


# -- trace -------------------------------------------------------------------

def test_stream_trace_serialization_roundtrips(star):
    rate = star.nominal_rate(0.4)
    tr = run_stream(star, horizon=10 / rate, seed=5, rate=rate,
                    window_s=0.5 / rate, max_batch=3, solver_latency=0.01,
                    drain="exact", finish=True)
    blob = json.loads(json.dumps(tr.to_dict()))
    assert blob["windows"] == len(tr.windows)
    assert len(blob["requests"]) == len(tr.requests)
    assert blob["requests"][0]["latency_s"] == pytest.approx(
        tr.requests[0].latency_s)
    # the satellite fix: serialized traces keep the exact-drain results
    assert blob["completions"] == tr.completions
    assert "p99_actual_s" in blob and "p99_wait_s" in blob
    assert blob["sustained_arr_s"] == pytest.approx(tr.sustained_arr_s())


def test_pipeline_rejects_backwards_stream(star):
    jobs = _jobs(star, 2)
    with pytest.raises(ValueError, match="backwards"):
        _pipe(star, window_s=0.0, max_batch=1).run(
            iter([(1.0, [jobs[0]]), (0.5, [jobs[1]])]),
            pad_to=star.max_layers)


def test_measured_latency_uses_observed_walls(star):
    """solver_latency='measured' charges an EMA of real solve walls to the
    clock: after the first (free) window, commits trail closes."""
    jobs = _jobs(star, 4)
    stream = [(float(i), [j]) for i, j in enumerate(jobs)]
    tr = _pipe(star, window_s=0.0, max_batch=1,
               solver_latency="measured").run(
        iter(stream), horizon=10.0, pad_to=star.max_layers)
    assert tr.windows[0].solve_model_s == 0.0  # no observation yet
    walls = [w.solve_wall_s for w in tr.windows]
    assert all(w > 0 for w in walls)
    assert all(w.solve_model_s > 0 for w in tr.windows[1:])
