"""jit'd public wrappers around the tropical kernels.

``minplus_matmul`` dispatches to a Pallas kernel when the problem is big
enough to amortize tiling (and pads to block multiples with +INF, which is
absorbing for ``min``), otherwise to the pure-jnp oracle.  Batched operands
(any leading stack dims, flattened to one batch axis) go to the batched
kernel, so ``[L+1, V, V]`` and ``[J, L+1, V, V]`` closure stacks stay on the
tiled path.  On CPU the kernels run in interpret mode — the TPU is the
target, CPU validates semantics.

``minplus_dispatch`` is the pure (shape -> path) decision function, exposed
so tests and benchmarks can introspect dispatch without running the kernel;
``dispatch_counts`` tallies which path each traced ``minplus_matmul`` took.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp

from . import ref
from .minplus import minplus_matmul_pallas, minplus_matmul_pallas_batched

_PAD = jnp.float32(1e30)
# Below this dimension the [n, n, n] broadcast oracle is cheaper than tiling.
_PALLAS_MIN_DIM = 256

# Trace-time tally of dispatch decisions (jit caching means a hit is recorded
# once per traced shape, not once per execution) — introspection/testing aid.
_DISPATCH_COUNTS: collections.Counter = collections.Counter()


def dispatch_counts() -> dict[str, int]:
    """Copy of the {path: times-traced} tally ("oracle" | "pallas_2d" |
    "pallas_batched").

    These counters fire at **trace time**, not execution time: a jitted
    caller records each kernel choice once per compiled signature, then
    every cached re-execution runs the chosen kernel without touching the
    tally.  The distinction matters most for the fused greedy solver —
    its whole round loop (J rounds x closure squarings per round) is one
    device program, so a solve that *executes* hundreds of min-plus
    kernels adds at most a handful of entries here (and a warmed shape
    adds zero).  Per-solve execution telemetry lives in the solver's
    plan meta instead: ``meta["dispatches"]`` / ``meta["rounds_per_
    dispatch"]`` count what the device actually ran.

    Raises ``RuntimeError`` when called under an active trace: the tally
    mid-trace is a partial mixture of finished and in-flight tracings, so
    any number read there silently over/under-counts (and a traced reader
    would bake the stale snapshot into the compiled program as a
    constant).
    """
    if not jax.core.trace_ctx.is_top_level():
        raise RuntimeError(
            "dispatch_counts() called under an active jax trace: the "
            "trace-time tally is mid-update, and a traced reader would "
            "bake a stale snapshot into the compiled program. Read it "
            "from host driver code after the traced call returns.")
    return dict(_DISPATCH_COUNTS)


def reset_dispatch_counts() -> None:
    _DISPATCH_COUNTS.clear()


def minplus_dispatch(a_shape: tuple[int, ...],
                     b_shape: tuple[int, ...] | None = None,
                     *, use_pallas: bool | None = None) -> str:
    """Which path ``minplus_matmul`` takes for these operand shapes.

    Returns ``"oracle"``, ``"pallas_2d"``, or ``"pallas_batched"``.  The
    decision is purely shape-based (and therefore static under jit): the
    Pallas kernels win once every contraction dim reaches ``_PALLAS_MIN_DIM``
    (or when forced via ``use_pallas=True``); mismatched leading batch dims
    always fall back to the broadcasting oracle.
    """
    b_shape = tuple(a_shape) if b_shape is None else tuple(b_shape)
    a_shape = tuple(a_shape)
    if len(a_shape) < 2 or len(b_shape) < 2 or a_shape[:-2] != b_shape[:-2]:
        return "oracle"
    m, k = a_shape[-2:]
    n = b_shape[-1]
    big = (_should_use_pallas(m, k, n) if use_pallas is None else use_pallas)
    if not big:
        return "oracle"
    return "pallas_2d" if len(a_shape) == 2 else "pallas_batched"


def _should_use_pallas(m: int, k: int, n: int) -> bool:
    return min(m, k, n) >= _PALLAS_MIN_DIM


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def minplus_matmul(a: jax.Array, b: jax.Array, *, use_pallas: bool | None = None,
                   block: int = 128) -> jax.Array:
    """C[..., i, j] = min_k A[..., i, k] + B[..., k, j].

    2-D operands use the tiled kernel; operands with (matching) leading
    batch dims are flattened to one batch axis and use the batched kernel
    (leading batch grid dimension).  Small problems and mismatched batch
    shapes use the broadcast oracle.
    """
    kind = minplus_dispatch(a.shape, b.shape, use_pallas=use_pallas)
    _DISPATCH_COUNTS[kind] += 1
    if kind == "oracle":
        return ref.minplus_matmul_ref(a, b)

    m, k = a.shape[-2:]
    n = b.shape[-1]
    pm, pk, pn = (-m) % block, (-k) % block, (-n) % block
    if kind == "pallas_2d":
        a_p = jnp.pad(a, ((0, pm), (0, pk)), constant_values=_PAD)
        b_p = jnp.pad(b, ((0, pk), (0, pn)), constant_values=_PAD)
        out = minplus_matmul_pallas(
            a_p, b_p, bm=block, bn=block, bk=block,
            interpret=_interpret_default())
        return out[:m, :n]

    lead = a.shape[:-2]
    a3 = a.reshape((-1, m, k))
    b3 = b.reshape((-1, k, n))
    a_p = jnp.pad(a3, ((0, 0), (0, pm), (0, pk)), constant_values=_PAD)
    b_p = jnp.pad(b3, ((0, 0), (0, pk), (0, pn)), constant_values=_PAD)
    out = minplus_matmul_pallas_batched(
        a_p, b_p, bm=block, bn=block, bk=block,
        interpret=_interpret_default())
    return out[:, :m, :n].reshape(lead + (m, n))


def minplus_matvec(a: jax.Array, x: jax.Array) -> jax.Array:
    return ref.minplus_matvec_ref(a, x)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def minplus_closure(w: jax.Array, *, use_pallas: bool | None = None) -> jax.Array:
    """All-pairs shortest-path distances by repeated tropical squaring.

    ``w``: [V, V] (or batched [..., V, V]) edge weights, INF-sentinel for
    absent edges. Returns D with D[u, u] = 0 and D[u, v] = min-cost path.

    After s squarings d covers all paths of <= 2^s hops and simple paths
    have at most V-1, so ``ceil(log2(V-1))`` squarings always suffice — but
    real topologies converge in ``ceil(log2(diameter))`` squarings, so the
    loop is a ``lax.while_loop`` that exits as soon as ``d == minplus(d, d)``
    (squaring a fixed point reproduces it bit-for-bit, so the early exit is
    exact).  Batched stacks exit when every batch element has converged.
    Both 2-D and batched operands stay on the Pallas path via
    :func:`minplus_matmul` dispatch.
    """
    n = w.shape[-1]
    eye = jnp.arange(n)
    d = w.at[..., eye, eye].min(0.0)
    steps = max(1, (n - 1).bit_length())

    def cond(state):
        _, i, converged = state
        return jnp.logical_and(i < steps, jnp.logical_not(converged))

    def body(state):
        d, i, _ = state
        d2 = minplus_matmul(d, d, use_pallas=use_pallas)
        return d2, i + 1, jnp.all(d2 == d)

    d, _, _ = jax.lax.while_loop(
        cond, body, (d, jnp.int32(0), jnp.asarray(False)))
    return d


# ---------------------------------------------------------------------------
# Flash attention (kernels/flash.py) with a memory-bounded XLA backward.
# ---------------------------------------------------------------------------

def _attn_ref_bhsd(q, k, v, scale):
    """Chunk-free reference math (used under jax.vjp for the backward)."""
    s = jnp.einsum("bsd,btd->bst", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    n = q.shape[1]
    mask = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bst,btd->bsd", p.astype(q.dtype), v)


@functools.lru_cache(maxsize=None)
def _make_flash(scale: float, bq: int, bk: int, interpret: bool):
    from .flash import flash_fwd_lse, flash_bwd

    @jax.custom_vjp
    def fn(q, k, v):
        o, _ = flash_fwd_lse(q, k, v, scale=scale, causal=True,
                             bq=bq, bk=bk, interpret=interpret)
        return o

    def fwd(q, k, v):
        o, lse = flash_fwd_lse(q, k, v, scale=scale, causal=True,
                               bq=bq, bk=bk, interpret=interpret)
        return o, (q, k, v, o, lse)

    def bwd(res, g):
        q, k, v, o, lse = res
        return flash_bwd(q, k, v, o, lse, g, scale=scale, causal=True,
                         bq=bq, bk=bk, interpret=interpret)

    fn.defvjp(fwd, bwd)
    return fn


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    scale: float, bq: int = 512, bk: int = 512,
                    interpret: bool | None = None) -> jax.Array:
    """Causal flash attention on [BH, S, d] operands (see kernels/flash.py).

    Forward runs the Pallas kernel (scores never reach HBM); backward
    recomputes attention under jax.vjp of the reference math (remat-style).
    """
    if interpret is None:
        interpret = _interpret_default()
    bq = min(bq, q.shape[1])
    bk = min(bk, k.shape[1])
    return _make_flash(float(scale), int(bq), int(bk), bool(interpret))(
        q, k, v)
