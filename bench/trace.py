"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Reads the file with ``jax.profiler.ProfileData`` and returns:

* ``busy_s`` — the union of the intervals in which an operation ran on the
  device, averaged over the devices in the trace;
* ``program_s`` — per program name asked for, the summed time of its
  executions: the device's ``XLA Modules`` events whose name holds it, or,
  where the trace has no device (a CPU run), the host's
  ``PjitFunction(<name>)`` dispatch events;
* ``spans`` — the host annotations named ``bench.*`` and how often each
  appears;
* ``breakdown`` — the device programs that took most time (the
  ``XLA Modules`` events, named without their fingerprint), and the
  longest idle gaps between device operations, each named by the
  innermost ``bench.*`` host span open at its midpoint.
"""
from __future__ import annotations

import collections
import pathlib

TOP = 10


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _host_spans(planes):
    spans = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name[len("bench."):]))
    return spans


def _name_at(spans, t: float) -> str:
    inner = None
    for s, e, name in spans:
        if s <= t <= e and (inner is None or e - s < inner[1] - inner[0]):
            inner = (s, e, name)
    return inner[2] if inner else "pipeline"


def reduce(trace_dir, *, programs=()) -> dict:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    planes = list(ProfileData.from_file(str(files[-1])).planes)
    devices = [p for p in planes if p.name.startswith("/device:")]
    program_s = collections.Counter()
    module_s = collections.Counter()
    busy_total, gaps = 0.0, []
    spans = _host_spans(planes)
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        ops = lines.get("XLA Ops") or lines.get("XLA Modules")
        if ops is None:
            continue
        iv = [(ev.start_ns, ev.start_ns + ev.duration_ns)
              for ev in ops.events]
        merged = _merge(iv)
        busy_total += sum(e - s for s, e in merged) * 1e-9
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            gaps.append((s1 - e0, e0, s1))
        mods = lines.get("XLA Modules")
        for ev in (mods.events if mods is not None else ()):
            module_s[ev.name.split("(")[0]] += ev.duration_ns * 1e-9
            for name in programs:
                if name in ev.name:
                    program_s[name] += ev.duration_ns * 1e-9
    if not devices:
        for plane in planes:
            for line in plane.lines:
                for ev in line.events:
                    for name in programs:
                        if ev.name == f"PjitFunction({name})":
                            program_s[name] += ev.duration_ns * 1e-9
    gaps.sort(reverse=True)
    return {
        "devices": len(devices),
        "busy_s": busy_total / max(len(devices), 1),
        "program_s": dict(program_s),
        "spans": dict(collections.Counter(name for _, _, name in spans)),
        "breakdown": {
            "device_ops": [[n, s] for n, s in module_s.most_common(TOP)],
            "idle_gaps": [[_name_at(spans, (s + e) / 2), g * 1e-9]
                          for g, s, e in gaps[:TOP]],
        },
    }
