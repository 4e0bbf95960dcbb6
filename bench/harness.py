"""One run of one cell: set-up, the timed window, the metrics, the check.

The system under test is the served path: ``StreamingPipeline.run`` over
an ``OnlineScheduler(drain="exact")`` with the fused greedy solver.  The
harness hands it lazy ``(t, jobs)`` epochs from :mod:`traffic` and the
scheduler's simulated solver latency is 0, so the simulation is a
deterministic function of the seed; speed is wall time, and the stream is
consumed as fast as the served path places it.

Set-up builds the deployment, then runs the cell's own traffic from a
fixed warm-up seed for ``warmup_windows`` windows: that compiles exactly
the shapes this traffic reaches and brings the exact drain's backlog to
its steady state.  The timed window continues on the same scheduler with
the ``--seed`` stream, and ends once ``seconds`` of wall time have passed
(the pipeline then commits what is in flight).

Spans are taken from here, at the calls into each layer (the solver
registry entry ``greedy``, ``OnlineScheduler.advance_to`` and
``submit_window``), each inside a ``jax.profiler.TraceAnnotation`` when
the run is traced.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import importlib
import itertools
import json
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import check as K, traffic as T

WARMUP_SEED = 0x5EEDB0A7
# A traced run traces the first seconds of its window: a trace of every
# device op of a whole window takes minutes to write.
TRACE_SECONDS = 3.0


class Spans:
    """Host spans by name: ``(start, end, window index)``."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.by_name: dict[str, list] = collections.defaultdict(list)
        self.window = -1

    @contextlib.contextmanager
    def span(self, name: str):
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
        else:
            ann = contextlib.nullcontext()
        t0 = time.perf_counter()
        with ann:
            yield
        self.by_name[name].append((t0, time.perf_counter(), self.window))


class GcClock:
    """Seconds the interpreter spent collecting garbage while installed."""

    def __init__(self):
        self.total = self.longest = 0.0
        self._t0 = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            d = time.perf_counter() - self._t0
            self.total += d
            self.longest = max(self.longest, d)
            self._t0 = None


@dataclasses.dataclass
class RunView:
    """What a per-layer metric reader reads."""

    spans: Spans
    timed: list                 # indices of the timed window's batches
    wall_s: float               # the timed window's wall seconds
    solves: list                # (batch index, compiled) per solver call
    trace: dict | None = None   # bench/trace.py's reduction, traced runs
    trace_window_s: float = 0.0  # wall seconds the trace covers

    def per_window(self, name: str) -> dict:
        """Summed span seconds per timed batch."""
        out = dict.fromkeys(self.timed, 0.0)
        for t0, t1, w in self.spans.by_name.get(name, ()):
            if w in out:
                out[w] += t1 - t0
        return out

    def durations(self, name: str) -> list:
        timed = set(self.timed)
        return [t1 - t0 for t0, t1, w in self.spans.by_name.get(name, ())
                if w in timed]


@contextlib.contextmanager
def instrumented(sched, spans: Spans, windows: list, solves: list,
                 phase: dict):
    """Wrap the calls into each layer; restore them on exit."""
    from repro.core import solvers
    greedy = solvers.get("greedy")
    advance_to, submit_window = sched.advance_to, sched.submit_window

    def solve(net, batch, **opts):
        with spans.span("solve"):
            plan = greedy(net, batch, **opts)
        solves.append((spans.window, bool(plan.meta.get("jit_compiled"))))
        return plan

    def drain(t):
        with spans.span("drain"):
            advance_to(t)

    def decide(t, infer_jobs, **kw):
        spans.window = len(windows)
        rec = {"t": float(t), "names": [j.name for j in infer_jobs],
               "timed": phase["timed"]}
        windows.append(rec)
        with spans.span("decide"):
            placements = submit_window(t, infer_jobs, **kw)
        rec["placements"] = placements
        return placements

    solvers.register("greedy")(solve)
    sched.advance_to, sched.submit_window = drain, decide
    try:
        yield
    finally:
        solvers.register("greedy")(greedy)
        del sched.advance_to, sched.submit_window


def _placed(plan, j: int, name: str, num_layers: int) -> K.Placed:
    return K.Placed(name, [int(a) for a in plan.job_assign(j, num_layers)],
                    float(plan.bounds[j]),
                    [list(map(tuple, h)) for h in plan.paths[j]])


def _windows(raw: list, requests: dict, dep) -> list:
    """The harness's window records, in the form the check reads."""
    nl = {n: dep.profiles[q.kind][0].shape[0] for n, q in requests.items()}
    return [K.Window(t=rec["t"], names=rec["names"], timed=rec["timed"],
                     placed=[_placed(p.plan, p.job, p.job_name,
                                     nl[p.job_name])
                             for p in rec.get("placements", ())])
            for rec in raw]


def _limited(gen, stop: float, on_tick=None):
    """Pull from ``gen`` until the wall clock reaches ``stop``."""
    while time.perf_counter() < stop:
        if on_tick is not None:
            on_tick()
        yield next(gen)


def per_layer_readers(bench: dict, workload: str) -> dict:
    """The cell's per-layer metrics: name -> (unit, reader module)."""
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        out[m["name"]] = (m["unit"],
                          importlib.import_module(
                              f"bench.metrics.{m['name']}"))
    return out


def open_devices(want: int) -> list:
    """The first ``want`` TPU chips, with the persistent compile cache on.

    Exits non-zero, printing no result, when JAX finds no TPU or fewer
    chips than that."""
    import jax
    from repro.launch import compile_cache
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < want:
        print(f"bench: needs {want} TPU chip(s); JAX finds {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(1)
    compile_cache.enable()
    # Cache every program, however quick to compile, so that a rerun in
    # the same checkout compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return devices[:want]


def device_info(devices) -> dict:
    d = devices[0]
    peak = 0
    for dev in devices:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, bench: dict, devices, controls: bool = False,
             spec: dict | None = None) -> dict:
    """One run; returns the contract's result object.

    ``spec`` overrides the workload file (the tests run a cell at a size
    the CPU holds); ``controls`` also computes the lower-precision
    references' numbers (the check's controls), under ``"controls"``."""
    import jax
    from repro.serving.online import OnlineScheduler
    from repro.serving.stream import StreamConfig, StreamingPipeline

    t_entry = time.perf_counter()
    spec = spec or T.load_workload(workload)
    cfg, tr = spec["config_file"], spec["traffic"]
    dep = T.load_deployment(cfg)
    sched = OnlineScheduler(dep.scenario.topology, method="greedy",
                            drain="exact",
                            sim_engine=tr.get("drain_engine", "indexed"))
    pipe = StreamingPipeline(sched, StreamConfig(
        window_s=float(tr["window_s"]), max_batch=int(tr["max_batch"]),
        solve_mode="batched", solver_latency=0.0))
    spans = Spans(annotate=trace)
    raw, solves, requests = [], [], {}
    phase = {"timed": False}
    tracer = {"dir": None, "t0": None, "t1": None, "pause": 0.0}

    def stop_trace():
        if tracer["dir"] is not None and tracer["t1"] is None:
            tracer["t1"] = time.perf_counter()
            jax.profiler.stop_trace()
            # writing the trace is no work of the pipeline's
            tracer["pause"] = time.perf_counter() - tracer["t1"]

    t_warm = time.perf_counter()
    with instrumented(sched, spans, raw, solves, phase):
        warm = T.epochs(dep, tr, T.rng_for(WARMUP_SEED, 0), t0=0.0,
                        prefix="w", log=requests, timed=False)
        pipe.run(itertools.islice(warm, int(spec["warmup_windows"])),
                 pad_to=dep.max_layers)
        t_last = max(q.arrival for q in requests.values())
        timed = T.epochs(dep, tr, T.rng_for(seed, 1), t0=t_last,
                         prefix="s", log=requests, timed=True)
        n_set_up = len(raw)
        phase["timed"] = True
        if trace:
            tracer["dir"] = tempfile.mkdtemp(prefix="bench-trace-")
        t_open = time.perf_counter()
        stop = t_open + float(seconds)
        trace_stop = t_open + TRACE_SECONDS
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host spans, not every call
            jax.profiler.start_trace(tracer["dir"], profiler_options=opts)
            tracer["t0"] = time.perf_counter()

        def tick():
            if time.perf_counter() >= trace_stop:
                stop_trace()

        gc_clock = GcClock()
        gc.callbacks.append(gc_clock)
        try:
            pipe.run(_limited(timed, stop, tick if trace else None),
                     pad_to=dep.max_layers)
        finally:
            gc.callbacks.remove(gc_clock)
        t_close = time.perf_counter()
        stop_trace()
        if tracer["t1"] is not None and tracer["t1"] >= t_close:
            tracer["pause"] = 0.0      # stopped after the window closed
    device = device_info(devices)
    wall = t_close - t_open
    timed_idx = list(range(n_set_up, len(raw)))
    shed = list(sched.trace.shed)
    windows = _windows(raw, requests, dep)
    acct = K.accounting(requests, windows, shed)
    completions = dict(sched.ledger.completed)
    clock = float(sched.now)
    n_sample = min(int(spec["check"]["sample_windows"]), len(timed_idx))
    sample = sorted(T.rng_for(seed, 2).choice(
        timed_idx, size=n_sample, replace=False).tolist())
    del pipe, sched
    t_check = time.perf_counter()
    numbers = K.compare(dep, requests, windows, completions, clock,
                        sample=sample)
    check_s = time.perf_counter() - t_check
    numbers["unaccounted"] = acct["unaccounted"]
    limits = spec["limits"]
    ok, checks = K.judge({k: v for k, v in numbers.items() if k in limits},
                         limits)
    result = {"correct": ok, "attempted": acct["attempted"],
              "failed": acct["failed"] + acct["unaccounted"]}
    view = RunView(spans, timed_idx, wall - tracer["pause"], solves)
    if trace:
        from bench import trace as TR
        try:
            view.trace = TR.reduce(tracer["dir"],
                                   programs=("_fused_solve",))
        finally:
            shutil.rmtree(tracer["dir"], ignore_errors=True)
        view.trace_window_s = tracer["t1"] - tracer["t0"]
    layers = {}
    for name, (unit, mod) in per_layer_readers(bench, workload).items():
        v = mod.read(view)
        if v is not None:
            layers[name] = {"value": float(v), "unit": unit}
    if trace:
        result["metrics"] = layers
        device["busy_s"] = view.trace["busy_s"]
        device["window_s"] = view.trace_window_s
        result["breakdown"] = view.trace["breakdown"]
    else:
        timed_set, per_req = set(timed_idx), []
        for t0, t1, w in spans.by_name["decide"]:
            if w in timed_set:
                per_req += [(t1 - t0) * 1e3] * len(raw[w]["names"])
        result["metrics"] = {
            "placed_jobs_per_s": {"value": acct["placed"] / wall,
                                  "unit": "jobs/s"},
            "decision_ms_p50": {"value": float(np.percentile(per_req, 50)),
                                "unit": "ms"},
            "decision_ms_p95": {"value": float(np.percentile(per_req, 95)),
                                "unit": "ms"},
            "setup_s": {"value": t_open - t_start, "unit": "s"},
        }
    result["device"] = device
    result["checks"] = checks
    extra = {"not_compared": {k: v for k, v in numbers.items()
                              if k not in limits},
             # the per-layer readings this run could make, for the record
             "per_layer": {k: v["value"] for k, v in layers.items()},
             "windows": len(timed_idx), "placed": acct["placed"],
             "longest_between_batches_s": max(
                 [b[0] - a[1] for a, b in zip(spans.by_name["decide"],
                                              spans.by_name["decide"][1:])
                  if a[2] in set(timed_idx)] or [0.0]),
             "gc_s": gc_clock.total, "gc_longest_s": gc_clock.longest,
             "sample": len(sample),
             "wall_s": wall, "check_s": check_s, "setup_windows": n_set_up,
             "setup_parts_s": {"start_to_harness": t_entry - t_start,
                               "deployment": t_warm - t_entry,
                               "warmup": t_open - t_warm},
             "compiles_in_window": sum(1 for w, c in solves
                                       if c and w in set(timed_idx))}
    if controls:
        ctl = K.compare(dep, requests, windows, completions, clock,
                        sample=sample, controls=True)
        ctl["unaccounted"] = 0
        extra["controls"] = {k: {"value": float(v), "limit": limits.get(k)}
                             for k, v in ctl.items()}
    return {"result": result, "extra": extra}


def dumps(obj) -> str:
    """JSON with a non-finite number written as the string ``"inf"``,
    ``"-inf"`` or ``"nan"``."""
    def clean(x):
        if isinstance(x, float) and not np.isfinite(x):
            return str(x)
        if isinstance(x, dict):
            return {k: clean(v) for k, v in x.items()}
        if isinstance(x, (list, tuple)):
            return [clean(v) for v in x]
        return x
    return json.dumps(clean(obj), allow_nan=False)
