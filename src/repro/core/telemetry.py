"""In-program spans and counters for the served decision path.

One process-wide recorder.  ``span(name)`` times a block of host code (the
``spanned(name)`` decorator, each call of a function) and ``count(name)``
bumps an integer counter; ``snapshot()`` hands both back to the caller, and
nothing is written anywhere until then.

* Spans are off by default.  Off, ``span`` costs one flag check and returns
  one shared no-op context (no allocation, no clock read).  On, each span
  appends ``(name, t0_ns, t1_ns, parent, window)`` to an in-memory list:
  ``time.perf_counter_ns`` times, the list index of the enclosing open span
  (-1 at the top) and the window id the streaming pipeline set with
  :func:`set_window` when the commit group started.  With ``annotate`` the
  span also enters ``jax.profiler.TraceAnnotation("repro.<name>")``, so it
  lands in a profile on the same clock as the device's ops.
* The recorder follows the profiler: at each :func:`set_window` it turns
  itself on, annotated, while a profiler trace is being captured, and off
  again once that trace has stopped, unless :func:`enable` turned it on.
* Counters are always on (``plan.meta["closure_builds"]`` reads one in
  every run); each costs one dict increment.  ``snapshot()["counters"]``
  holds what they counted while the recorder was on; :func:`counter` reads
  the running total.  ``jit_misses`` counts the programs lowered for a new
  signature (jit cache misses, eager ops included), only while on.
* :func:`to_device` and :func:`to_host` are the served path's host<->device
  transfers, counted as ``h2d`` and ``d2h`` (one per call, which may carry
  a pytree; a ``d2h`` is one wait for the device), and their bytes as
  ``h2d_bytes`` and ``d2h_bytes`` (the summed ``nbytes`` of the pytree's
  array leaves).  ``batch_fetches`` counts the fetches of a job batch that
  had no host copy (``jobs.host_batch``): 0 on the served path, whose
  batches are built on the host.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import time

import jax

_device_put, _device_get = jax.device_put, jax.device_get

# The one counter store: name -> running total since the process started.
_COUNTS: collections.Counter = collections.Counter()

_NULL = contextlib.nullcontext()
_on = False          # recording spans (and jit misses)?
_annotate = False    # ...each inside a TraceAnnotation?
_following = False   # ...because a profiler trace is being captured?
_window = -1
_spans: list[list] = []   # [name, t0_ns, t1_ns, parent, window]
_open: list[int] = []     # indices of the open spans, innermost last
_base: dict = {}          # _COUNTS when the recorder was last turned on
_held: dict | None = None  # counts while on, frozen when turned off
_listening = False

# One lowering per program compiled for a new signature (the jaxpr-trace
# event fires once per traced function, nested jits included).
_MISS_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class _Span:
    __slots__ = ("rec", "idx", "ann")

    def __init__(self, name: str):
        self.rec = [name, 0, 0, _open[-1] if _open else -1, _window]
        self.ann = (jax.profiler.TraceAnnotation(f"repro.{name}")
                    if _annotate else None)

    def __enter__(self):
        self.idx = len(_spans)
        _spans.append(self.rec)
        _open.append(self.idx)
        if self.ann is not None:
            self.ann.__enter__()
        self.rec[1] = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.rec[2] = time.perf_counter_ns()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        if _open and _open[-1] == self.idx:
            _open.pop()
        return False


def span(name: str):
    """Context manager timing the enclosed block as span ``name``."""
    if not _on:
        return _NULL
    return _Span(name)


def spanned(name: str):
    """Decorator: each call of the function is span ``name``."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not _on:
                return fn(*args, **kwargs)
            with _Span(name):
                return fn(*args, **kwargs)
        return wrapper
    return deco


def count(name: str, n: int = 1) -> None:
    _COUNTS[name] += n


def counter(name: str) -> int:
    """Running total of counter ``name``."""
    return _COUNTS[name]


def _nbytes(x) -> int:
    """Summed ``nbytes`` of the array leaves of the pytree ``x`` (as size
    times item size: a jax array's ``nbytes`` costs a few times more)."""
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(x)
               if hasattr(leaf, "dtype"))


def to_device(x):
    """``jax.device_put(x)``, counted as one ``h2d`` of its bytes."""
    _COUNTS["h2d"] += 1
    _COUNTS["h2d_bytes"] += _nbytes(x)
    return _device_put(x)


def to_host(x):
    """``jax.device_get(x)``, counted as one ``d2h`` of its bytes."""
    _COUNTS["d2h"] += 1
    _COUNTS["d2h_bytes"] += _nbytes(x)
    return _device_get(x)


def call_counted(name: str, fn, *args, **kwargs):
    """Call the jitted ``fn`` once, counted as ``name``; returns ``(out,
    compiled)``, ``compiled`` true when the call grew ``fn``'s jit cache
    (it traced and compiled a new signature)."""
    _COUNTS[name] += 1
    size = fn._cache_size()
    out = fn(*args, **kwargs)
    return out, fn._cache_size() > size


def _on_event(event: str, duration_s: float, **kwargs) -> None:
    if _on and event == _MISS_EVENT:
        _COUNTS["jit_misses"] += 1


def enable(annotate: bool = False) -> None:
    """Clear the spans and turn the recorder on."""
    global _on, _annotate, _following, _base, _held, _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _listening = True
    _spans.clear()
    _open.clear()
    _base, _held = dict(_COUNTS), None
    _on, _annotate, _following = True, bool(annotate), False


def disable() -> None:
    """Turn the recorder off; what it recorded stays for :func:`snapshot`."""
    global _on, _annotate, _following, _held
    if _on:
        _held = _counted()
    _on = _annotate = _following = False


def _counted() -> dict:
    return {k: v - _base.get(k, 0) for k, v in _COUNTS.items()
            if v != _base.get(k, 0)}


def set_window(i: int) -> None:
    """Tag the spans that follow with window id ``i``; follow the
    profiler (see the module docstring)."""
    global _window, _following
    _window = int(i)
    tracing = jax.profiler.TraceAnnotation.is_enabled()
    if tracing and not _on:
        enable(annotate=True)
        _following = True
    elif _following and not tracing:
        disable()


def snapshot() -> dict:
    """``{"spans": [(name, t0_ns, t1_ns, parent, window), ...],
    "counters": {name: count while on}}``; spans still open are left
    out."""
    return {"spans": [tuple(r) for r in _spans if r[2]],
            "counters": _counted() if _on else dict(_held or {})}
