"""Shared neural-net building blocks (pure JAX, params are dict pytrees).

Conventions:
  * params are nested dicts of jnp arrays; block params are stacked along a
    leading layer axis and consumed by ``lax.scan`` (compact HLO => fast
    lowering/compiles even for 60-layer configs in the 512-device dry-run).
  * activations default to bfloat16, layernorm/softmax math in float32.
  * all shapes are static; masks implement causality / sliding windows.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def maybe_shard(x, *axes):
    """Activation-sharding anchor: constrain ``x`` to PartitionSpec(*axes).

    No-op unless an ambient mesh (jax.set_mesh) provides the named axes and
    the corresponding dims divide evenly — model code stays runnable on a
    single CPU device while the production-mesh dry-run gets explicit
    batch/tensor sharding anchors (GSPMD propagates the rest).
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    names = set(mesh.axis_names)

    def axis_size(a):
        if isinstance(a, tuple):
            return int(jnp.prod(jnp.array([mesh.shape[x] for x in a])))
        return mesh.shape[a]

    spec = []
    for i, a in enumerate(axes):
        if a is None:
            spec.append(None)
            continue
        parts = tuple(p for p in (a if isinstance(a, tuple) else (a,))
                      if p in names)   # drop axes this mesh doesn't have
        if not parts:
            spec.append(None)
            continue
        size = 1
        for p in parts:
            size *= mesh.shape[p]
        fits = x.shape[i] % size == 0
        spec.append((parts if len(parts) > 1 else parts[0]) if fits else None)
    return jax.lax.with_sharding_constraint(x, P(*spec))


DP = ("pod", "data")  # batch axes (pod collapses away on single-pod meshes)
TP = "model"


def remat_wrap(fn, cfg):
    """jax.checkpoint with the config's remat policy ('full' | 'dots')."""
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def truncated_normal(key, shape, dtype, scale):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


def dense_init(key, in_dim, out_dim, dtype, *, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    return truncated_normal(key, (in_dim, out_dim), dtype, scale)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_norm(key, d, kind: str, dtype):
    if kind == "rmsnorm":
        return {"w": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        return {"w": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}
    if kind == "nonparam_ln":  # OLMo: LayerNorm without affine params
        return {}
    raise ValueError(kind)


def apply_norm(params, x, kind: str, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        return (y * params["w"].astype(jnp.float32)).astype(x.dtype)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean((xf - mean) ** 2, -1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    if kind == "layernorm":
        y = y * params["w"].astype(jnp.float32) + params["b"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float = 1e4):
    """x: [..., S, H, hd]; positions: broadcastable to [..., S]."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # [hd/2]
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs  # [..., S,1,hd/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, causal / sliding-window / cross)
# ---------------------------------------------------------------------------

def init_attention(key, d_model, n_heads, n_kv, head_dim, dtype):
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d_model, n_heads * head_dim, dtype),
        "wk": dense_init(ks[1], d_model, n_kv * head_dim, dtype),
        "wv": dense_init(ks[2], d_model, n_kv * head_dim, dtype),
        "wo": dense_init(ks[3], n_heads * head_dim, d_model, dtype,
                         scale=1.0 / math.sqrt(n_heads * head_dim)),
    }


def _sdpa(q, k, v, mask, *, grouped=False):
    """q: [B,S,H,hd]; k/v: [B,T,Hkv,hd]; mask: [B?,1,S,T] bool or None.

    ``grouped=True`` contracts GQA via a grouped einsum instead of
    materializing ``jnp.repeat``ed K/V (a §Perf iteration: the repeat
    multiplies decode KV traffic by H/Hkv; math is identical).
    """
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    if rep > 1 and grouped:
        qg = q.reshape(b, s, hkv, rep, hd)
        scores = jnp.einsum("bsgrd,btgd->bgrst", qg, k).astype(jnp.float32)
        scores = scores.reshape(b, h, s, -1) / math.sqrt(hd)
        if mask is not None:
            scores = jnp.where(mask, scores, jnp.float32(-1e30))
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        pg = probs.reshape(b, hkv, rep, s, -1)
        out = jnp.einsum("bgrst,btgd->bsgrd", pg, v)
        return out.reshape(b, s, h, hd)
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def _sdpa_chunked(q, k, v, *, window: int, chunk: int, unroll: bool):
    """Query-chunked causal attention: identical math to :func:`_sdpa` with
    a causal (optionally sliding-window) mask, but the live score tensor is
    [B, H, chunk, T].  ``unroll=True`` (dry-run) emits each chunk in the
    HLO so cost analysis stays exact; otherwise chunks run under lax.map.
    """
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    if h // hkv > 1:
        k = jnp.repeat(k, h // hkv, axis=2)
        v = jnp.repeat(v, h // hkv, axis=2)
    n_chunks = s // chunk
    assert s % chunk == 0, (s, chunk)
    kpos = jnp.arange(s)[None, :]

    def one(i):
        qc = jax.lax.dynamic_slice_in_dim(q, i * chunk, chunk, 1)
        qpos = i * chunk + jnp.arange(chunk)[:, None]
        m = kpos <= qpos
        m &= (window <= 0) | (kpos > qpos - window)   # window may be traced
        sc = jnp.einsum("bshd,bthd->bhst", qc, k).astype(jnp.float32)
        sc = sc / math.sqrt(hd)
        sc = jnp.where(m[None, None], sc, jnp.float32(-1e30))
        pr = jax.nn.softmax(sc, axis=-1).astype(q.dtype)
        return jnp.einsum("bhst,bthd->bshd", pr, v)

    if unroll:
        return jnp.concatenate([one(i) for i in range(n_chunks)], axis=1)
    out = jax.lax.map(one, jnp.arange(n_chunks))      # [n, B, c, H, hd]
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, hd)


def causal_mask(s: int, t: int, window: int = 0):
    """[1,1,S,T] causal (optionally sliding-window) mask; t >= s offsets apply."""
    qpos = jnp.arange(s)[:, None] + (t - s)
    kpos = jnp.arange(t)[None, :]
    m = kpos <= qpos
    if window > 0:
        m &= kpos > qpos - window
    return m[None, None]


def _flash_bshd(q, k, v, *, scale=None):
    """[B,S,H,hd] -> flash attention kernel on [B*H, S, hd] (GQA repeated).

    Under an ambient mesh the kernel is shard_map'ed: batch shards over the
    dp axes and heads over 'model' (when divisible) — each device runs the
    Pallas kernel on its local [B/dp * H/tp, S, hd] block (GSPMD cannot
    partition a custom call, so without this the inputs would be
    all-gathered and the kernel replicated).
    """
    from repro.kernels import ops as kops
    b, s, h, hd = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    rep = h // k.shape[2]
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    def local(q_, k_, v_):
        b_, s_, h_, _ = q_.shape
        to_bhsd = lambda x: jnp.moveaxis(x, 2, 1).reshape(
            b_ * h_, s_, x.shape[-1])
        out = kops.flash_attention(to_bhsd(q_), to_bhsd(k_), to_bhsd(v_),
                                   scale=scale)
        return jnp.moveaxis(out.reshape(b_, h_, s_, -1), 1, 2)

    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return local(q, k, v)
    names = set(mesh.axis_names)
    dp = tuple(a for a in ("pod", "data") if a in names)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    dp_ax = dp if (dp and b % dp_size == 0) else None
    tp_ax = "model" if ("model" in names
                        and h % mesh.shape["model"] == 0) else None
    spec = P(dp_ax, None, tp_ax, None)
    # check_vma=False: pallas_call out_shapes carry no varying-mesh-axes info
    return jax.shard_map(local, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def attention(params, x, positions, *, n_heads, n_kv, head_dim,
              rope_theta=1e4, window=0, kv_cache=None, cache_pos=None,
              use_rope=True, chunk_q=0, unroll_chunks=False,
              attn_impl="xla", grouped=False):
    """Self-attention. With ``kv_cache`` = {'k','v'} [B, T, n_kv, hd], runs a
    decode step: writes K/V at ``cache_pos`` and attends over <= cache_pos."""
    b, s, d = x.shape
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    q = q.reshape(b, s, n_heads, head_dim)
    k = k.reshape(b, s, n_kv, head_dim)
    v = v.reshape(b, s, n_kv, head_dim)
    if use_rope:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    if kv_cache is None:
        if attn_impl == "flash" and window == 0 and s >= 128:
            out = _flash_bshd(q, k, v)
        elif chunk_q > 0 and s % chunk_q == 0 and s > chunk_q:
            out = _sdpa_chunked(q, k, v, window=window, chunk=chunk_q,
                                unroll=unroll_chunks)
        else:
            out = _sdpa(q, k, v, causal_mask(s, s, window), grouped=grouped)
        new_cache = None
    else:
        t = kv_cache["k"].shape[1]
        ck = jax.lax.dynamic_update_slice_in_dim(kv_cache["k"], k.astype(kv_cache["k"].dtype), cache_pos, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(kv_cache["v"], v.astype(kv_cache["v"].dtype), cache_pos, axis=1)
        kpos = jnp.arange(t)[None, :]
        valid = kpos <= (cache_pos + s - 1)     # decode chunks use s == 1
        if window > 0:
            valid &= kpos > (cache_pos + s - 1 - window)
        mask = valid[None, None]                # [1,1,1,T]
        out = _sdpa(q, ck.astype(q.dtype), cv.astype(q.dtype), mask,
                    grouped=grouped)
        new_cache = {"k": ck, "v": cv}
    out = out.reshape(b, s, n_heads * head_dim) @ params["wo"]
    return out, new_cache


def init_cross_attention(key, d_model, n_heads, head_dim, dtype):
    return init_attention(key, d_model, n_heads, n_heads, head_dim, dtype)


def cross_attention(params, x, enc, *, n_heads, head_dim):
    b, s, d = x.shape
    t = enc.shape[1]
    q = (x @ params["wq"]).reshape(b, s, n_heads, head_dim)
    k = (enc @ params["wk"]).reshape(b, t, n_heads, head_dim)
    v = (enc @ params["wv"]).reshape(b, t, n_heads, head_dim)
    out = _sdpa(q, k, v, None)
    return out.reshape(b, s, n_heads * head_dim) @ params["wo"]


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(key, d_model, d_ff, dtype, *, gated=True):
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], d_model, d_ff, dtype),
         "w_down": dense_init(ks[1], d_ff, d_model, dtype,
                              scale=1.0 / math.sqrt(d_ff))}
    if gated:
        p["w_gate"] = dense_init(ks[2], d_model, d_ff, dtype)
    return p


def mlp(params, x, *, gated=True, act=jax.nn.silu):
    up = x @ params["w_up"]
    if gated:
        up = act(x @ params["w_gate"]) * up
    else:
        up = act(up)
    return up @ params["w_down"]


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def init_embed(key, vocab, d_model, dtype, *, tie=True):
    ks = jax.random.split(key, 2)
    p = {"tok": truncated_normal(ks[0], (vocab, d_model), dtype, 0.02)}
    if not tie:
        p["head"] = dense_init(ks[1], d_model, vocab, dtype)
    return p


def embed(params, tokens):
    return jnp.take(params["tok"], tokens, axis=0)


def unembed(params, x):
    if "head" in params:
        return x @ params["head"]
    return x @ params["tok"].T


def pad_vocab(vocab: int, multiple: int = 256) -> int:
    return ((vocab + multiple - 1) // multiple) * multiple
