"""Mixture-of-Experts block: token-choice top-k routing, static capacity.

Dispatch is sort-based (no [N, E] one-hots): flatten the (token, choice)
pairs, sort by expert id, compute within-expert ranks from segment starts,
scatter into a static [E, C, D] buffer (drops beyond capacity), run a single
grouped einsum ``ecd,edf->ecf`` per projection, and scatter-add the weighted
results back.  The [E, ...] axes shard over the 'model' mesh axis (expert
parallelism); token axes shard over 'data' — GSPMD lowers the
dispatch/return as all-to-alls on the production mesh.

Shared experts (deepseek-v2) run densely on every token.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as cm


def init_moe(key, cfg):
    ks = jax.random.split(key, 6)
    e, d, f = cfg.moe_num_experts, cfg.d_model, cfg.moe_d_ff
    def ed(k, i, o, n):
        return jax.vmap(lambda kk: cm.dense_init(kk, i, o, cfg.dtype))(
            jax.random.split(k, n))
    p = {
        "router": cm.dense_init(ks[0], d, e, jnp.float32),
        "w_gate": ed(ks[1], d, f, e),    # [E, D, F]
        "w_up": ed(ks[2], d, f, e),      # [E, D, F]
        "w_down": ed(ks[3], f, d, e),    # [E, F, D]
    }
    if cfg.moe_num_shared > 0:
        p["shared"] = cm.init_mlp(ks[4], d, f * cfg.moe_num_shared, cfg.dtype)
    return p


def _ranks_in_expert(sorted_e: jax.Array) -> jax.Array:
    """Within-segment rank for a sorted id vector (segment = equal ids)."""
    n = sorted_e.shape[0]
    idx = jnp.arange(n)
    is_start = jnp.concatenate([jnp.ones((1,), bool), sorted_e[1:] != sorted_e[:-1]])
    seg_start = jax.lax.associative_scan(jnp.maximum, jnp.where(is_start, idx, 0))
    return idx - seg_start


def moe_block(p, x, cfg):
    """Dispatch wrapper: optionally shard_map the dispatch per data shard.

    The global-sort dispatch makes GSPMD all-gather the token stream (the
    argsort is cross-device), replicating the [E*C, D] buffers on every
    device — the dominant memory+collective term of the MoE train cells
    (EXPERIMENTS §Perf, cell A).  ``moe_local_dispatch`` sorts and buckets
    per data shard instead (experts gathered, tokens local), which is plain
    data-parallel MoE: capacity is enforced per shard, communication reduces
    to the expert-weight gathers.
    """
    if cfg.moe_local_dispatch:
        mesh = jax.sharding.get_abstract_mesh()
        if not mesh.empty:
            names = set(mesh.axis_names)
            # dispatch over ALL mesh axes (batch over data *and* model) —
            # restricting to the data axes replicates the dispatch across
            # 'model' and multiplies compute (measured: §Perf cell A it4)
            dp = tuple(a for a in ("pod", "data", "model") if a in names)
            while dp:
                size = 1
                for a in dp:
                    size *= mesh.shape[a]
                if x.shape[0] % size == 0:
                    break
                dp = dp[:-1]
            if dp:
                from jax.sharding import PartitionSpec as P
                spec_x = P(dp, None, None)
                return jax.shard_map(
                    lambda p_, x_: _moe_block_impl(p_, x_, cfg),
                    in_specs=(P(), spec_x), out_specs=spec_x,
                    check_vma=False)(p, x)
    return _moe_block_impl(p, x, cfg)


def _moe_block_impl(p, x, cfg):
    b, s, d = x.shape
    n = b * s
    e, k = cfg.moe_num_experts, cfg.moe_top_k
    cap = int(cfg.moe_capacity_factor * n * k / e) + 1

    if n <= 64:
        # decode-sized token counts: give every token guaranteed capacity
        # (cap = n) so single-token routing matches prefill exactly
        cap = n

    xf = x.reshape(n, d)
    logits = (xf.astype(jnp.float32) @ p["router"])           # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)                    # [N, k]
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    flat_e = top_e.reshape(n * k)
    flat_w = top_w.reshape(n * k)
    flat_tok = jnp.repeat(jnp.arange(n), k)

    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    ranks = _ranks_in_expert(sorted_e)                        # [N*k]
    keep = ranks < cap
    slot = sorted_e * cap + ranks                             # [N*k] in [0, E*C)
    slot = jnp.where(keep, slot, e * cap)                     # overflow bin

    buf = jnp.zeros((e * cap + 1, d), cfg.dtype)
    buf = buf.at[slot].set(xf[flat_tok[order]])
    buf = buf[: e * cap].reshape(e, cap, d)
    if cfg.moe_ep_shard:
        buf = cm.maybe_shard(buf, "model", None, None)   # EP over experts

    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"]))
    up = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    out = jnp.einsum("ecf,efd->ecd", gate * up, p["w_down"])  # [E, C, D]
    if cfg.moe_ep_shard:
        out = cm.maybe_shard(out, "model", None, None)

    out_flat = out.reshape(e * cap, d)
    gathered = jnp.where(keep[:, None], out_flat[jnp.minimum(slot, e * cap - 1)], 0)
    y = jnp.zeros((n, d), cfg.dtype)
    y = y.at[flat_tok[order]].add(gathered * flat_w[order][:, None].astype(cfg.dtype))

    if "shared" in p:
        y = y + cm.mlp(p["shared"], xf)
    return y.reshape(b, s, d)
