"""Pallas TPU kernel: tropical (min, +) matrix multiply.

This is the compute hot-spot of the paper's routing framework at scale: the
all-pairs transfer-cost closures (one per DNN layer, per candidate routing)
are computed by repeated min-plus squaring, each squaring a V x V x V
tropical contraction.

TPU adaptation (see DESIGN.md §3.3): the MXU performs multiply-accumulate
only, so a (min, +) contraction cannot use the systolic array.  It *is*
however a perfectly regular dense contraction, so the memory-hierarchy
discipline of a matmul kernel still applies verbatim: stream (bm, bk) /
(bk, bn) tiles HBM->VMEM, keep a (bm, bn) running-min accumulator in VMEM
scratch across the K grid dimension, and emit the tile once on the last K
step.  Inside the tile the contraction is VPU work: bk rank-1 broadcast-adds
followed by elementwise minimum, with fully aligned (8, 128)-lane shapes when
bm, bn are multiples of 128.

Grid: (M/bm, N/bn, K/bk), K innermost (sequential on TPU, so the VMEM
accumulator carries across K steps of the same (i, j) tile).

Batched variant: the routing pipeline squares whole closure *stacks* —
``[L+1, V, V]`` (one matrix per DNN layer) or ``[U, L+1, V, V]`` (per
deduplicated job) — so :func:`minplus_matmul_pallas_batched` adds a leading
**batch grid dimension**: grid ``(B, M/bm, N/bn, K/bk)`` with block shapes
``(1, bm, bk)`` / ``(1, bk, bn)`` / ``(1, bm, bn)``.  Each batch element is
an independent (parallel) slice of the grid reusing the same VMEM
accumulator discipline; K stays innermost/sequential.  Higher-rank stacks
are flattened to one batch axis in :mod:`repro.kernels.ops` before reaching
the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _contract_tile(acc, a_cols, b_rows, *, bk: int, inner_chunk: int):
    """Fold one (bm, bk) x (bk, bn) tile pair into the running-min ``acc``.

    bk is contracted in chunks: each chunk materializes a [bm, chunk, bn]
    broadcast-sum in VREGs/VMEM and folds it into the accumulator with a
    running min (chunk=8 keeps the intermediate at 512 KiB for 128-blocks).
    The chunk loop is unrolled at trace time so every K-chunk is read from
    the refs at a static offset: Mosaic lowers static ref slices, but not a
    ``dynamic_slice`` of a loaded value.  ``a_cols(s)`` / ``b_rows(s)``
    read the [bm, chunk] columns / [chunk, bn] rows at slice ``s``.
    """
    for c in range(bk // inner_chunk):
        s = slice(c * inner_chunk, (c + 1) * inner_chunk)
        a_c = a_cols(s).astype(jnp.float32)
        b_c = b_rows(s).astype(jnp.float32)
        part = jnp.min(a_c[:, :, None] + b_c[None, :, :], axis=1)  # [bm, bn]
        acc = jnp.minimum(acc, part)
    return acc


def _minplus_kernel(a_ref, b_ref, o_ref, acc_ref, *, bk: int, k_steps: int,
                    inner_chunk: int):
    """One (bm, bn) output tile; min-accumulate over the K grid dim."""
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, jnp.float32(3.0e38) / 2)

    acc_ref[...] = _contract_tile(
        acc_ref[...], lambda s: a_ref[:, s], lambda s: b_ref[s, :],
        bk=bk, inner_chunk=inner_chunk)

    @pl.when(k_idx == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "inner_chunk", "interpret"))
def minplus_matmul_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    inner_chunk: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """C = A (min,+) B for 2-D operands whose dims divide the block sizes.

    Shape padding / batching live in :mod:`repro.kernels.ops`; this function
    is the raw tiled kernel.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (a.shape, b.shape, (bm, bn, bk))
    assert bk % inner_chunk == 0
    k_steps = k // bk

    kernel = functools.partial(
        _minplus_kernel, bk=bk, k_steps=k_steps, inner_chunk=inner_chunk)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b)


def _minplus_kernel_batched(a_ref, b_ref, o_ref, acc_ref, *, bk: int,
                            k_steps: int, inner_chunk: int):
    """One (bm, bn) output tile of one batch element; min-accumulate over K."""
    k_idx = pl.program_id(3)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, jnp.float32(3.0e38) / 2)

    acc_ref[...] = _contract_tile(
        acc_ref[...], lambda s: a_ref[0, :, s], lambda s: b_ref[0, s, :],
        bk=bk, inner_chunk=inner_chunk)

    @pl.when(k_idx == k_steps - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "inner_chunk", "interpret"))
def minplus_matmul_pallas_batched(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    inner_chunk: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """C[b] = A[b] (min,+) B[b] for [B, M, K] x [B, K, N] operands.

    The batch axis is the leading (parallel) grid dimension; within a batch
    element the tiling/accumulator scheme is identical to
    :func:`minplus_matmul_pallas`.  M, N, K must divide the block sizes —
    padding and flattening of higher-rank stacks live in
    :mod:`repro.kernels.ops`.
    """
    bsz, m, k = a.shape
    bsz2, k2, n = b.shape
    assert bsz == bsz2 and k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        (a.shape, b.shape, (bm, bn, bk))
    assert bk % inner_chunk == 0
    k_steps = k // bk

    kernel = functools.partial(
        _minplus_kernel_batched, bk=bk, k_steps=k_steps,
        inner_chunk=inner_chunk)
    return pl.pallas_call(
        kernel,
        grid=(bsz, m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda bb, i, j, kk: (bb, i, kk)),
            pl.BlockSpec((1, bk, bn), lambda bb, i, j, kk: (bb, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda bb, i, j, kk: (bb, i, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(a, b)
