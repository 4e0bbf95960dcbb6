"""Where a served decision's time goes, read from the program's own spans.

  PYTHONPATH=src python benchmarks/decision_spans.py \\
      --workload usb-paper.b1 --seed 7 --out chiprun_out/spans.json

Runs one benchmark cell's served path in one process, with the
deployment, warm-up and traffic of ``bench/harness.py`` and its host spans
at the calls into each layer, and reports from ``repro.core.telemetry``:

1. ``overhead``: the decision's median with the recorder off and on (no
   profiler), in alternating segments of the same stream;
2. ``agreement``: per batch (median over the batches recorded), the
   program's ``sched.submit_window``, ``solve`` and ``sched.drain`` next to
   the harness's ``decide``, ``solve`` and ``drain``, how much of ``solve``
   and of ``sched.submit_window`` their child spans cover, every span's
   wall and every counter per batch;
3. ``profile``: a profiled segment (the recorder follows the profiler):
   the device's busy share, the longest idle gaps between device ops, each
   named by the innermost ``bench.*`` or ``repro.*`` span open at its
   midpoint, the idle seconds under each innermost program span, and the
   device time of ``_fused_solve``'s ops by the named scope of
   ``_fused_rounds`` their stats carry, with one op event's stats.

On a TPU it enables the checkout's compile cache as the benchmark does;
elsewhere it runs on what JAX finds (a rehearsal: its times are not the
device's).
"""
from __future__ import annotations

import argparse
import bisect
import collections
import itertools
import json
import pathlib
import shutil
import statistics
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
SCOPES = ("closure", "route_fwd", "commit")


def _median(xs):
    return float(statistics.median(xs)) if xs else None


def host_spans(planes) -> list:
    """``(start_ns, end_ns, name)`` of every ``bench.*``/``repro.*`` host
    annotation in a profile, sorted by start."""
    out = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("bench.", "repro.")):
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name))
    return sorted(out)


class Innermost:
    """The innermost span open at an instant: a sweep over properly nested
    spans, ``(start_ns, end_ns, name)``, into elementary intervals."""

    def __init__(self, spans):
        events = sorted([(s, 1, -e, i) for i, (s, e, _) in enumerate(spans)]
                        + [(e, 0, -s, i) for i, (s, e, _) in enumerate(spans)])
        self.cuts, self.names, stack = [], [], []
        for t, opening, _, i in events:
            if opening:
                stack.append(i)
            else:
                stack.remove(i)
            if self.cuts and self.cuts[-1] == t:
                self.cuts.pop()
                self.names.pop()
            self.cuts.append(t)
            self.names.append(spans[stack[-1]][2] if stack else None)

    def at(self, t: float) -> str | None:
        i = bisect.bisect_right(self.cuts, t) - 1
        return self.names[i] if i >= 0 else None

    def split(self, a: float, b: float):
        """``(seconds, name)`` of the pieces of ``[a, b]``."""
        lo, hi = bisect.bisect_right(self.cuts, a), bisect.bisect_left(
            self.cuts, b)
        pts = [a] + self.cuts[lo:hi] + [b]
        return [((y - x) * 1e-9, self.at((x + y) / 2))
                for x, y in zip(pts, pts[1:])]


def idle_by_span(gaps, spans) -> dict:
    """Idle seconds under each innermost ``repro.*`` span (``"none"``
    where no program span is open): each gap is cut at every span
    boundary inside it."""
    prog = Innermost([x for x in spans if x[2].startswith("repro.")])
    out = collections.Counter()
    for a, b in gaps:
        for sec, name in prog.split(a, b):
            out[name or "none"] += sec
    return dict(out.most_common())


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_profile(trace_dir, top: int = 12) -> dict:
    from jax.profiler import ProfileData
    path = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                  key=lambda p: p.stat().st_mtime)[-1]
    planes = list(ProfileData.from_file(str(path)).planes)
    spans = host_spans(planes)
    out = {"devices": 0, "busy_s": 0.0, "window_s": 0.0, "idle_gaps": [],
           "idle_by_program_span": {}, "scope_events": {}, "sample_op": None,
           "scoped_op": None}
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        ops = lines.get("XLA Ops")
        if ops is None:
            continue
        out["devices"] += 1
        merged = _merge((ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in ops.events)
        out["busy_s"] = sum(e - s for s, e in merged) * 1e-9
        if merged:
            out["window_s"] = (merged[-1][1] - merged[0][0]) * 1e-9
        gaps = [(e0, s1) for (_, e0), (s1, _) in zip(merged, merged[1:])]
        ranked = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
        both = Innermost(spans)
        bench = Innermost([x for x in spans if x[2].startswith("bench.")])
        out["idle_gaps"] = [
            [both.at((a + b) / 2) or "pipeline",
             bench.at((a + b) / 2) or "pipeline", (b - a) * 1e-9]
            for a, b in ranked]
        out["idle_by_program_span"] = idle_by_span(gaps, spans)
        mods = lines.get("XLA Modules")
        solve = _merge((ev.start_ns, ev.start_ns + ev.duration_ns)
                       for ev in (mods.events if mods else ())
                       if "_fused_solve" in ev.name)
        starts = [s for s, _ in solve]
        scope_s = collections.Counter()
        for ev in ops.events:
            i = bisect.bisect_right(starts, ev.start_ns) - 1
            if i < 0 or ev.start_ns > solve[i][1]:
                continue
            stats = {str(k): str(v) for k, v in ev.stats}
            text = " ".join(stats.values())
            hit = [s for s in SCOPES if f"/{s}/" in text or
                   text.endswith(f"/{s}")] or ["(none)"]
            scope_s[hit[0]] += ev.duration_ns * 1e-9
            if out["sample_op"] is None:
                out["sample_op"] = {"name": ev.name, "stats": stats}
            if out["scoped_op"] is None and hit[0] != "(none)":
                out["scoped_op"] = {"name": ev.name, "stats": stats}
        out["scope_events"] = dict(scope_s)
        break
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--segment-s", type=float, default=2.0)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--profile-s", type=float, default=2.0,
                    help="seconds of the profiled segment; 0 skips it")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    from bench import harness, traffic as T
    from repro.core import telemetry
    from repro.serving.online import OnlineScheduler
    from repro.serving.stream import StreamConfig, StreamingPipeline

    if jax.default_backend() == "tpu":
        harness.open_devices(1)
    spec = T.load_workload(args.workload)
    tr = spec["traffic"]
    dep = T.load_deployment(spec["config_file"])
    sched = OnlineScheduler(dep.scenario.topology, method="greedy",
                            drain="exact",
                            sim_engine=tr.get("drain_engine", "indexed"))
    pipe = StreamingPipeline(sched, StreamConfig(
        window_s=float(tr["window_s"]), max_batch=int(tr["max_batch"]),
        solve_mode="batched", solver_latency=0.0))
    spans = harness.Spans(annotate=True)
    raw, solves, requests = [], [], {}
    phase = {"timed": False}
    report = {"workload": args.workload, "seed": args.seed,
              "device": jax.devices()[0].device_kind}

    with harness.instrumented(sched, spans, raw, solves, phase):
        warm = T.epochs(dep, tr, T.rng_for(harness.WARMUP_SEED, 0), t0=0.0,
                        prefix="w", log=requests, timed=False)
        pipe.run(itertools.islice(warm, int(spec["warmup_windows"])),
                 pad_to=dep.max_layers)
        t_last = max(q.arrival for q in requests.values())
        stream = T.epochs(dep, tr, T.rng_for(args.seed, 1), t0=t_last,
                          prefix="s", log=requests, timed=True)

        def segment(seconds: float) -> range:
            n0 = len(raw)
            pipe.run(harness._limited(stream, time.perf_counter() + seconds),
                     pad_to=dep.max_layers)
            return range(n0, len(raw))

        def per_request_ms(ws) -> list:
            ws = set(ws)
            return [(t1 - t0) * 1e3
                    for t0, t1, w in spans.by_name["decide"] if w in ws
                    for _ in raw[w]["names"]]

        # 1. overhead: off/on alternating, on first in every other round
        walls = {"off": [], "on": []}
        recorded, ratios = [], []
        for r in range(args.rounds):
            p50 = {}
            for mode in (("off", "on") if r % 2 == 0 else ("on", "off")):
                if mode == "on":
                    telemetry.enable()
                ws = segment(args.segment_s)
                if mode == "on":
                    telemetry.disable()
                    recorded.append((ws, telemetry.snapshot()))
                walls[mode] += per_request_ms(ws)
                p50[mode] = _median(per_request_ms(ws))
            ratios.append(p50["on"] / p50["off"] - 1.0)
        report["overhead"] = {
            m: {"decision_ms_p50": _median(v), "requests": len(v)}
            for m, v in walls.items()}
        off, on = (report["overhead"][m]["decision_ms_p50"]
                   for m in ("off", "on"))
        report["overhead"]["on_over_off"] = on / off - 1.0
        report["overhead"]["per_round_on_over_off"] = ratios

        # 2. agreement and the per-batch breakdown
        report["agreement"] = agreement(spans, recorded)

        if args.profile_s <= 0:
            return finish(report, args.out)

        # 3. a profiled segment
        tdir = tempfile.mkdtemp(prefix="decision-spans-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            ws = segment(args.profile_s)
        finally:
            jax.profiler.stop_trace()
        snap = telemetry.snapshot()
        telemetry.disable()
        try:
            report["profile"] = reduce_profile(tdir)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        report["profile"]["batches"] = len(ws)
        report["profile"]["counters"] = snap["counters"]

    finish(report, args.out)


def finish(report: dict, out: str | None) -> None:
    line = json.dumps(report)
    if out:
        pathlib.Path(out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(out).write_text(line + "\n")
    print(line)


def agreement(spans, recorded) -> dict:
    """Per-batch program spans against the harness's, over the batches
    the recorder saw (``recorded``: (harness batch indices, snapshot))."""
    rows = collections.defaultdict(list)
    counters = collections.Counter()
    n_batches = 0
    harness_ms = {name: {} for name in ("decide", "solve", "drain")}
    for name, per in harness_ms.items():
        for t0, t1, w in spans.by_name[name]:
            per[w] = per.get(w, 0.0) + (t1 - t0) * 1e3
    for ws, snap in recorded:
        sp = snap["spans"]
        counters.update(snap["counters"])
        n_batches += len(ws)
        by_window = collections.defaultdict(list)
        for i, s in enumerate(sp):
            by_window[s[4]].append(i)
        roots = sorted(by_window, key=lambda k: sp[by_window[k][0]][1])
        if len(roots) != len(ws):
            raise RuntimeError(f"{len(roots)} recorded windows for "
                               f"{len(ws)} harness batches")
        for w, key in zip(ws, roots):
            idx = by_window[key]
            total = collections.Counter()
            kids = collections.Counter()
            for i in idx:
                name, t0, t1, parent, _ = sp[i]
                total[name] += (t1 - t0) * 1e-6
                if parent >= 0:
                    kids[sp[parent][0]] += (t1 - t0) * 1e-6
            for name, ms in total.items():
                rows[f"span.{name}"].append(ms)
            for prog, harn in (("sched.submit_window", "decide"),
                               ("solve", "solve"), ("sched.drain", "drain")):
                rows[f"vs.{prog}"].append(
                    (total[prog], harness_ms[harn].get(w, 0.0)))
            for name in ("solve", "sched.submit_window"):
                if total[name]:
                    rows[f"cover.{name}"].append(kids[name] / total[name])
    out = {"batches": n_batches,
           "per_batch_ms": {k[5:]: _median(v) for k, v in rows.items()
                            if k.startswith("span.")},
           "children_cover": {k[6:]: _median(v) for k, v in rows.items()
                              if k.startswith("cover.")},
           "counters_per_batch": {k: v / max(n_batches, 1)
                                  for k, v in counters.items()}}
    out["program_vs_harness_ms"] = {
        k[3:]: [_median([p for p, _ in v]), _median([h for _, h in v])]
        for k, v in rows.items() if k.startswith("vs.")}
    return out


if __name__ == "__main__":
    main()
