"""The trace reduction finds a named jitted program and a host span in a
profile recorded here on the CPU."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import trace  # noqa: E402


@jax.jit
def bench_probe_program(x):
    return jnp.sin(x) @ x


def test_reduction_finds_program_and_span(tmp_path):
    x = jnp.ones((64, 64))
    bench_probe_program(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.solve"):
                bench_probe_program(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    out = trace.reduce(tmp_path, programs=("bench_probe_program",))
    assert out["spans"].get("solve") == 3
    assert out["program_s"]["bench_probe_program"] > 0
    assert out["devices"] == 0 and out["busy_s"] == 0.0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_merge_and_gap_naming():
    assert trace._merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    spans = [(0, 100, "decide"), (10, 20, "solve")]
    assert trace._name_at(spans, 15) == "solve"
    assert trace._name_at(spans, 50) == "decide"
    assert trace._name_at(spans, 150) == "pipeline"
