"""Pallas TPU kernel: causal flash attention (online-softmax blocking).

The §Roofline baseline showed every training cell memory-bound on the
materialized [B, H, S, S] score tensors (deepseek-v2 train_4k: 274 s memory
term vs 7.5 s compute).  Flash attention is the canonical TPU adaptation:
stream K/V tiles HBM->VMEM, keep the running (max, sum, weighted-acc) of the
online softmax in VMEM scratch, and never write scores to HBM — per-device
attention HBM traffic collapses from O(S^2) to O(S * d).

Layout: q/k/v are [BH, S, d] (batch*heads flattened outside; GQA/MLA map
onto this by repeating K/V heads / concatenating nope+rope dims — see
ops.flash_attention).  Grid (BH, nq, nk), nk innermost and sequential, with
f32 scratch accumulators carried across the nk dimension.  Causal blocks
with k_start > q_end are skipped entirely (pl.when), halving work.

Backward: also Pallas (``flash_bwd``): the forward saves only O and the
logsumexp L; dq/dk/dv kernels recompute p = exp(s - L) per tile, so the
backward's HBM traffic is O(S * d) as well.  ops.flash_attention wires the
three kernels through jax.custom_vjp.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # python float: jnp constants must not be closure-captured


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                  *, bq: int, bk: int, nk: int, scale: float, causal: bool):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * bq
    k_start = ik * bk
    # causal: this k block overlaps queries iff k_start <= q_end
    live = (not causal) or (k_start <= q_start + bq - 1)

    @pl.when(live)
    def _block():
        q = q_ref[0].astype(jnp.float32)                    # [bq, d]
        k = k_ref[0].astype(jnp.float32)                    # [bk, d]
        v = v_ref[0].astype(jnp.float32)                    # [bk, dv]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                       # [bq, bk]
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])                     # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)
        acc = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new
        acc_ref[...] = acc

    @pl.when(ik == nk - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "causal", "scale",
                                             "interpret"))
def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         scale: float, causal: bool = True, bq: int = 512,
                         bk: int = 512, interpret: bool = False) -> jax.Array:
    """q, k: [BH, S, d]; v: [BH, S, dv] -> [BH, S, dv]."""
    bh, s, d = q.shape
    dv = v.shape[-1]
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    nq, nk = s // bq, s // bk

    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, nk=nk,
                               scale=scale, causal=causal)
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Backward kernels.  Standard flash-attention backward with logsumexp L
# saved by the forward:  p = exp(s - L);  dv = p^T do;  dp = do v^T;
# ds = p * (dp - rowsum(do * o));  dq = ds k;  dk = ds^T q.
# Two kernels: dq accumulates over kv blocks (grid nk innermost); dk/dv
# accumulate over q blocks (grid nq innermost).
# ---------------------------------------------------------------------------

def _flash_fwd_lse_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                          acc_ref, m_ref, l_ref,
                          *, bq: int, bk: int, nk: int, scale: float,
                          causal: bool):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * bq
    k_start = ik * bk
    live = (not causal) or (k_start <= q_start + bq - 1)

    @pl.when(live)
    def _block():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l)


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, acc_ref,
                     *, bq: int, bk: int, nk: int, scale: float,
                     causal: bool):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start, k_start = iq * bq, ik * bk
    live = (not causal) or (k_start <= q_start + bq - 1)

    @pl.when(live)
    def _block():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _done():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dk_acc, dv_acc,
                      *, bq: int, bk: int, nq: int, scale: float,
                      causal: bool):
    ik = pl.program_id(1)
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start, k_start = iq * bq, ik * bk
    live = (not causal) or (k_start <= q_start + bq - 1)

    @pl.when(live)
    def _block():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                       # [bq, bk]
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [bk, dv]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale              # [bq, bk]
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [bk, d]

    @pl.when(iq == nq - 1)
    def _done():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "causal", "scale",
                                             "interpret"))
def flash_fwd_lse(q, k, v, *, scale, causal=True, bq=512, bk=512,
                  interpret=False):
    bh, s, d = q.shape
    dv = v.shape[-1]
    bq, bk = min(bq, s), min(bk, s)
    nq, nk = s // bq, s // bk
    kernel = functools.partial(_flash_fwd_lse_kernel, bq=bq, bk=bk, nk=nk,
                               scale=scale, causal=causal)
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq), lambda b, i, j: (b, i)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bh, s, dv), q.dtype),
                   jax.ShapeDtypeStruct((bh, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, dv), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "causal", "scale",
                                             "interpret"))
def flash_bwd(q, k, v, o, lse, do, *, scale, causal=True, bq=512, bk=512,
              interpret=False):
    bh, s, d = q.shape
    dv = v.shape[-1]
    bq, bk = min(bq, s), min(bk, s)
    nq, nk = s // bq, s // bk
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, bq=bq, bk=bk, nk=nk, scale=scale,
                          causal=causal),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dv), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq), lambda b, i, j: (b, i)),
            pl.BlockSpec((1, bq), lambda b, i, j: (b, i)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk, dv_out = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, bq=bq, bk=bk, nq=nq, scale=scale,
                          causal=causal),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, dv), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bq, dv), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq), lambda b, j, i: (b, i)),
            pl.BlockSpec((1, bq), lambda b, j, i: (b, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, dv), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, s, dv), q.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv_out
