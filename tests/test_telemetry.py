"""The in-program recorder (``core/telemetry.py``) on the served decision
path: ``StreamingPipeline`` over ``OnlineScheduler(drain="exact")`` with the
fused greedy solver, in one-job and 32-job windows."""
import jax
import jax._src.api as jax_api
import numpy as np
import pytest
from jax._src import array as jax_array
from jax._src.lib import guard_lib

from repro.core import greedy, jobs as J, telemetry
from repro.scenarios import make_scenario
from repro.serving.online import OnlineScheduler
from repro.serving.stream import StreamConfig, StreamingPipeline

SOLVE_CHILDREN = {"greedy.stage", "greedy.dispatch", "greedy.fetch",
                  "greedy.paths", "greedy.assemble"}
DECISION_CHILDREN = {"sched.drain", "sched.topology", "sched.backlog",
                     "sched.batch", "solve", "sched.commit"}


class Served:
    """One warmed served path; ``window()`` commits the next window."""

    def __init__(self, per_window: int, engine: str):
        self.sc = make_scenario("us-backbone:paper", seed=0)
        self.rng = np.random.default_rng(per_window)
        self.gap = per_window / self.sc.nominal_rate(0.8)
        self.t = 0.0
        sched = OnlineScheduler(self.sc.topology, method="greedy",
                                drain="exact", sim_engine=engine)
        self.pipe = StreamingPipeline(sched, StreamConfig(
            window_s=0.0, max_batch=per_window, solve_mode="batched",
            solver_latency=0.0))
        self.per_window = per_window
        self.pad_to = self.sc.max_layers
        self.window(3)       # compiles every program the path runs

    def window(self, n: int = 1):
        epochs = []
        for _ in range(n):
            self.t += self.gap
            epochs.append((self.t, self.sc.sample_jobs(self.rng,
                                                       self.per_window)))
        trace = self.pipe.run(epochs, pad_to=self.pad_to)
        # the pipeline sheds a window whose solve raised: none may
        assert not trace.shed, trace.shed[0]


@pytest.fixture(scope="module", params=[1, 32], ids=["b1", "b32"])
def served(request):
    engine = "indexed" if request.param == 1 else "ref"
    yield Served(request.param, engine)
    telemetry.disable()


def _recorded(served, n: int) -> dict:
    telemetry.enable()
    try:
        served.window(n)
    finally:
        telemetry.disable()
    return telemetry.snapshot()


def test_recorder_off_records_no_span(served):
    _recorded(served, 1)
    telemetry.disable()
    before = telemetry.snapshot()["spans"]
    served.window(1)
    assert telemetry.snapshot()["spans"] == before
    assert telemetry.span("solve") is telemetry.span("sched.drain")


def test_spans_nest_inside_their_parents(served):
    spans = _recorded(served, 2)["spans"]
    names = {s[0] for s in spans}
    assert SOLVE_CHILDREN | DECISION_CHILDREN <= names
    child_s = [0] * len(spans)
    for name, t0, t1, parent, window in spans:
        assert t0 <= t1
        if parent < 0:
            assert name == "pipeline.commit"
            continue
        p = spans[parent]
        assert p[1] <= t0 and t1 <= p[2], (name, p[0])
        assert window == p[4]
        child_s[parent] += t1 - t0
    for s, c in zip(spans, child_s):
        assert s[2] - s[1] - c >= 0, s[0]         # self time
    kids = {(spans[p][0], n) for n, _, _, p, _ in spans if p >= 0}
    assert {("solve", n) for n in SOLVE_CHILDREN} <= kids
    assert {("sched.submit_window", n) for n in DECISION_CHILDREN} <= kids


def test_one_submit_window_per_window_id(served):
    spans = _recorded(served, 3)["spans"]
    per_id = {}
    for name, *_, window in spans:
        per_id.setdefault(window, []).append(name)
    assert len(per_id) == 3
    for names in per_id.values():
        assert names.count("sched.submit_window") == 1
        assert names.count("pipeline.commit") == 1


def test_every_transfer_is_counted(served, monkeypatch):
    """Under ``transfer_guard("disallow")``, with jax's own transfer entry
    points refused and a device array's value readable only inside a
    ``device_get``, the served path moves data through ``to_device`` and
    ``to_host`` alone, so ``h2d``/``d2h`` miss nothing."""
    def refuse(*args, **kwargs):
        raise AssertionError("transfer outside telemetry.to_device/to_host")

    def outside_device_get():
        return not guard_lib.thread_local_state().explicit_device_get

    def checked(convert):
        # numpy reads a CPU array through the buffer protocol, which no
        # transfer guard sees: refuse it here as the chip would
        def conv(a, *args, **kwargs):
            if isinstance(a, jax.Array) and outside_device_get():
                raise AssertionError("implicit device-to-host read")
            return convert(a, *args, **kwargs)
        return conv

    value = jax_array.ArrayImpl._value

    def checked_value(self):
        if outside_device_get():
            raise AssertionError("implicit device-to-host read")
        return value.fget(self)

    for mod in (jax, jax_api):
        monkeypatch.setattr(mod, "device_put", refuse)
        monkeypatch.setattr(mod, "device_get", refuse)
    for name in ("asarray", "asanyarray", "array"):
        monkeypatch.setattr(np, name, checked(getattr(np, name)))
    monkeypatch.setattr(jax_array.ArrayImpl, "_value",
                        property(checked_value))
    h2d, d2h = telemetry.counter("h2d"), telemetry.counter("d2h")
    with jax.transfer_guard("disallow"):
        served.window(2)
    assert telemetry.counter("h2d") > h2d
    assert telemetry.counter("d2h") > d2h


def test_a_healthy_batch_moves_only_its_own_data(served):
    """After warm-up a healthy window builds no effective topology and
    fetches nothing the host holds (rates, queues, the batch): per batch
    one wait (the scan's one fetch of its results and hops) and 4 uploads
    (the staged batch with its dedupe plan and mask, two queue syncs, the
    clock)."""
    n = 3
    counters = _recorded(served, n)["counters"]
    assert counters.get("topology_builds", 0) == 0
    assert counters.get("batch_fetches", 0) == 0
    assert (counters["d2h"], counters["h2d"]) == (1 * n, 4 * n)


def _tree():
    """A pytree of 48 + 20 + 4 = 72 bytes of array leaves."""
    return {"a": np.zeros((3, 4), np.float32),
            "b": (np.arange(5, dtype=np.int32), np.float32(2.0))}


def test_transfer_bytes_are_the_leaves_nbytes():
    up, down = telemetry.counter("h2d_bytes"), telemetry.counter("d2h_bytes")
    on_device = telemetry.to_device(_tree())
    assert telemetry.counter("h2d_bytes") - up == 72
    back = telemetry.to_host(on_device)
    assert telemetry.counter("d2h_bytes") - down == 72
    assert sum(x.nbytes for x in jax.tree_util.tree_leaves(back)) == 72


def test_recorded_transfer_bytes_are_those_moved_while_on():
    telemetry.disable()
    telemetry.to_device(_tree())
    telemetry.enable()
    try:
        telemetry.to_host(telemetry.to_device(_tree()["a"]))
    finally:
        telemetry.disable()
    telemetry.to_host(telemetry.to_device(_tree()))
    counters = telemetry.snapshot()["counters"]
    assert (counters["h2d_bytes"], counters["d2h_bytes"]) == (48, 48)
    assert (counters["h2d"], counters["d2h"]) == (1, 1)


def _batch(sc, n, seed=0):
    return J.batch_jobs(sc.sample_jobs(np.random.default_rng(seed), n),
                        pad_to=sc.max_layers)


@pytest.mark.parametrize("on_host", [True, False], ids=["host", "device"])
def test_a_solve_with_paths_is_one_program_and_one_fetch(on_host):
    """``greedy_route(extract_paths=True)`` on a k=4 fat-tree runs one
    device program and uploads once (the padded batch, the dedupe plan and
    the mask).  A host batch costs one wait, the fetch of the round
    outputs with their hops, and fetches exactly those bytes; a device
    batch (the fallback) costs one fetch of the batch more, counted in
    ``batch_fetches``."""
    sc = make_scenario("fat-tree:paper", seed=0, k=4)
    host = J.batch_jobs_host(sc.sample_jobs(np.random.default_rng(0), 4),
                             pad_to=sc.max_layers)
    net, batch = sc.topology.view(), host if on_host else _batch(sc, 4)
    greedy.greedy_route(net, batch, extract_paths=True)
    rounds, _, _ = jax.eval_shape(greedy._fused_solve, net,
                                  *greedy._stage_window(host))
    fetched = tuple(rounds) if on_host else (host,) + tuple(rounds)
    n0 = telemetry.counter("fused_dispatches")
    before = {k: telemetry.counter(k)
              for k in ("h2d", "d2h", "d2h_bytes", "batch_fetches")}
    with jax.transfer_guard("disallow"):
        plan = greedy.greedy_route(net, batch, extract_paths=True)
    moved = {k: telemetry.counter(k) - v for k, v in before.items()}
    assert telemetry.counter("fused_dispatches") - n0 == 1
    assert (moved["h2d"], moved["d2h"]) == (1, 1 if on_host else 2)
    assert moved["batch_fetches"] == (0 if on_host else 1)
    assert moved["d2h_bytes"] == sum(
        np.dtype(x.dtype).itemsize * int(np.prod(x.shape))
        for x in jax.tree_util.tree_leaves(fetched))
    assert set(plan.paths) == set(range(batch.num_jobs))


def test_fused_dispatches_count_executions():
    """Three warmed solves are three executions (a trace-time tally would
    read 0 once the shape is cached)."""
    sc = make_scenario("us-backbone:paper", seed=0)
    net, batch = sc.topology.view(), _batch(sc, 4)
    greedy.greedy_route(net, batch)
    n0 = telemetry.counter("fused_dispatches")
    for _ in range(3):
        greedy.greedy_route(net, batch)
    assert telemetry.counter("fused_dispatches") - n0 == 3


def test_jit_misses_count_new_programs_only():
    sc = make_scenario("us-backbone:paper", seed=0)
    net = sc.topology.view()
    warm, fresh = _batch(sc, 2), _batch(sc, 16, seed=1)
    greedy.greedy_route(net, warm)
    telemetry.enable()
    try:
        greedy.greedy_route(net, warm)
        warmed = telemetry.snapshot()["counters"].get("jit_misses", 0)
        plan = greedy.greedy_route(net, fresh)
        new = telemetry.snapshot()["counters"].get("jit_misses", 0)
    finally:
        telemetry.disable()
    assert warmed == 0
    assert new == 1 and plan.meta["jit_compiled"] is True


def test_recorder_follows_the_profiler(tmp_path):
    telemetry.disable()
    telemetry.set_window(0)
    assert telemetry.span("solve") is telemetry.span("solve")
    jax.profiler.start_trace(str(tmp_path))
    try:
        telemetry.set_window(1)
        with telemetry.span("solve"):
            telemetry.count("probe")
    finally:
        jax.profiler.stop_trace()
    telemetry.set_window(2)
    with telemetry.span("solve"):
        telemetry.count("probe")
    snap = telemetry.snapshot()
    assert [s[0] for s in snap["spans"]] == ["solve"]
    assert snap["spans"][0][4] == 1
    assert snap["counters"] == {"probe": 1}


def test_spanned_decorator_records_each_call():
    @telemetry.spanned("probe")
    def f(x):
        with telemetry.span("inner"):
            return x + 1

    telemetry.enable()
    try:
        assert [f(i) for i in range(2)] == [1, 2]
    finally:
        telemetry.disable()
    spans = telemetry.snapshot()["spans"]
    assert [s[0] for s in spans] == ["probe", "inner", "probe", "inner"]
    assert [s[3] for s in spans] == [-1, 0, -1, 2]


def test_profile_names_time_by_innermost_program_span(tmp_path):
    """Annotated program spans land in a profile on its clock, nested in
    the harness's ``bench.*`` spans; the innermost program span names the
    time under it (``benchmarks/decision_spans.py``'s reduction)."""
    import importlib.util
    import pathlib
    from jax.profiler import ProfileData
    path = (pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
            / "decision_spans.py")
    spec = importlib.util.spec_from_file_location("decision_spans", path)
    DS = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(DS)

    telemetry.disable()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        telemetry.set_window(0)
        with jax.profiler.TraceAnnotation("bench.decide"):
            with telemetry.span("solve"):
                with telemetry.span("greedy.fetch"):
                    np.linalg.inv(np.eye(64) * 2.0)
                np.linalg.inv(np.eye(64) * 2.0)
    finally:
        jax.profiler.stop_trace()
        telemetry.disable()
    xplane = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    spans = DS.host_spans(ProfileData.from_file(str(xplane)).planes)
    by_name = {name: (s, e) for s, e, name in spans}
    assert set(by_name) == {"bench.decide", "repro.solve",
                            "repro.greedy.fetch"}
    (d0, d1), (s0, s1), (f0, f1) = (by_name[n] for n in (
        "bench.decide", "repro.solve", "repro.greedy.fetch"))
    assert d0 <= s0 <= f0 <= f1 <= s1 <= d1
    inner = DS.Innermost(spans)
    assert inner.at((f0 + f1) / 2) == "repro.greedy.fetch"
    assert inner.at((f1 + s1) / 2) == "repro.solve"
    idle = DS.idle_by_span([(d0, d1)], spans)
    assert idle["repro.greedy.fetch"] == pytest.approx((f1 - f0) * 1e-9)
    assert idle["repro.solve"] == pytest.approx((s1 - s0 - f1 + f0) * 1e-9)
    assert sum(idle.values()) == pytest.approx((d1 - d0) * 1e-9)
