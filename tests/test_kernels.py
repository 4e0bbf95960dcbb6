"""Pallas tropical-matmul kernel vs. pure-jnp oracle (shape/dtype sweep)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.minplus import minplus_matmul_pallas


@pytest.mark.parametrize("m,k,n", [
    (128, 128, 128), (256, 128, 384), (128, 256, 128), (384, 384, 256),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_matches_ref(m, k, n, dtype):
    ka, kb = jax.random.split(jax.random.PRNGKey(m * k + n))
    a = (jax.random.uniform(ka, (m, k)) * 10).astype(dtype)
    b = (jax.random.uniform(kb, (k, n)) * 10).astype(dtype)
    out = minplus_matmul_pallas(a, b, interpret=True)
    want = ref.minplus_matmul_ref(a.astype(jnp.float32),
                                  b.astype(jnp.float32))
    tol = 1e-6 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), want, atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("m,k,n", [(1, 1, 1), (5, 7, 3), (130, 250, 90),
                                   (300, 300, 300)])
def test_padded_wrapper(m, k, n):
    ka, kb = jax.random.split(jax.random.PRNGKey(7))
    a = jax.random.uniform(ka, (m, k)) * 5
    b = jax.random.uniform(kb, (k, n)) * 5
    out = ops.minplus_matmul(a, b, use_pallas=True)
    np.testing.assert_allclose(out, ref.minplus_matmul_ref(a, b), rtol=1e-6)


def test_inf_padding_is_absorbing():
    a = jnp.full((4, 4), 1e30)
    b = jnp.ones((4, 4))
    out = ops.minplus_matmul(a, b, use_pallas=True)
    assert np.all(np.asarray(out) >= 1e29)


def test_closure_vs_dijkstra():
    import networkx as nx
    rng = np.random.default_rng(0)
    n = 17
    W = np.full((n, n), 1e30, np.float32)
    g = nx.gnp_random_graph(n, 0.3, seed=5, directed=True)
    for u, v in g.edges:
        W[u, v] = rng.uniform(0.1, 4)
    D = np.asarray(ops.minplus_closure(jnp.asarray(W)))
    gg = nx.DiGraph()
    gg.add_nodes_from(range(n))
    for u, v in g.edges:
        gg.add_edge(u, v, weight=float(W[u, v]))
    lens = dict(nx.all_pairs_dijkstra_path_length(gg))
    for u in range(n):
        for v in range(n):
            want = lens[u].get(v)
            if want is None:
                assert D[u, v] > 1e29
            elif u == v:
                assert D[u, v] == 0.0
            else:
                np.testing.assert_allclose(D[u, v], want, rtol=1e-5)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_closure_properties(seed):
    """closure is idempotent and satisfies the triangle inequality."""
    rng = np.random.default_rng(seed)
    n = 8
    W = np.where(rng.random((n, n)) < 0.4,
                 rng.uniform(0.1, 5, (n, n)), 1e30).astype(np.float32)
    D = np.asarray(ops.minplus_closure(jnp.asarray(W)))
    D2 = np.asarray(ops.minplus_closure(jnp.asarray(D)))
    np.testing.assert_allclose(D, D2, rtol=1e-5)   # idempotent
    via = np.min(D[:, :, None] + D[None, :, :], axis=1)
    assert np.all(D <= via + 1e-3 * np.abs(via))    # triangle inequality


def test_batched_ref():
    a = jax.random.uniform(jax.random.PRNGKey(0), (3, 8, 8))
    b = jax.random.uniform(jax.random.PRNGKey(1), (3, 8, 8))
    out = ref.minplus_matmul_ref(a, b)
    for i in range(3):
        np.testing.assert_allclose(out[i],
                                   ref.minplus_matmul_ref(a[i], b[i]),
                                   rtol=1e-6)


# ---------------------------------------------------------------------------
# Batched Pallas kernel (leading batch grid dimension) + early-exit closure
# ---------------------------------------------------------------------------

def _inf_sparse(rng, shape, density=0.4):
    return np.where(rng.random(shape) < density,
                    rng.uniform(0.1, 5.0, shape), 1e30).astype(np.float32)


@pytest.mark.parametrize("b,m,k,n", [(3, 128, 128, 128), (2, 128, 256, 128)])
def test_batched_kernel_matches_ref(b, m, k, n):
    from repro.kernels.minplus import minplus_matmul_pallas_batched
    rng = np.random.default_rng(b * m + n)
    a = jnp.asarray(_inf_sparse(rng, (b, m, k)))
    bb = jnp.asarray(_inf_sparse(rng, (b, k, n)))
    out = minplus_matmul_pallas_batched(a, bb, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.minplus_matmul_ref(a, bb)),
                               rtol=1e-6)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_batched_wrapper_random_sparse(seed):
    """Batched Pallas (forced) == broadcast oracle on INF-sparse stacks with
    non-multiple-of-block shapes."""
    rng = np.random.default_rng(seed)
    b = int(rng.integers(1, 4))
    m, k, n = (int(rng.integers(1, 140)) for _ in range(3))
    a = jnp.asarray(_inf_sparse(rng, (b, m, k)))
    bb = jnp.asarray(_inf_sparse(rng, (b, k, n)))
    out = ops.minplus_matmul(a, bb, use_pallas=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.minplus_matmul_ref(a, bb)),
                               rtol=1e-6)


def test_batched_wrapper_multi_lead_dims():
    """[J, L+1, V, V] stacks flatten to one batch axis and round-trip."""
    rng = np.random.default_rng(7)
    a = jnp.asarray(_inf_sparse(rng, (2, 3, 36, 36)))
    out = ops.minplus_matmul(a, a, use_pallas=True)
    assert out.shape == a.shape
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.minplus_matmul_ref(a, a)),
                               rtol=1e-6)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.booleans())
def test_closure_early_exit_matches_unconditional(seed, batched):
    """The while_loop early exit returns the same fixed point, bit for bit,
    as the unconditional (n-1).bit_length() squaring loop: the exit only
    skips squarings that provably reproduce d, so the sequences coincide."""
    rng = np.random.default_rng(seed)
    n = 9
    shape = (3, n, n) if batched else (n, n)
    w = jnp.asarray(_inf_sparse(rng, shape, density=0.3))
    got = np.asarray(ops.minplus_closure(w))
    eye = jnp.arange(n)
    d = w.at[..., eye, eye].min(0.0)
    for _ in range((n - 1).bit_length()):
        d = ops.minplus_matmul(d, d)
    assert np.array_equal(got, np.asarray(d))
    # and the fixed point is semantically the true closure
    np.testing.assert_allclose(got, np.asarray(ref.minplus_closure_ref(w)),
                               rtol=1e-5)


def test_minplus_dispatch_decisions():
    """Shape -> kernel-path decision table (dispatch introspection)."""
    # batched [L+1, V, V] stacks with V >= the threshold hit the batched kernel
    assert ops.minplus_dispatch((9, 256, 256)) == "pallas_batched"
    assert ops.minplus_dispatch((33, 512, 512)) == "pallas_batched"
    assert ops.minplus_dispatch((4, 9, 256, 256)) == "pallas_batched"
    # 2-D operands keep the 2-D kernel
    assert ops.minplus_dispatch((256, 256)) == "pallas_2d"
    # small problems stay on the broadcast oracle
    assert ops.minplus_dispatch((9, 64, 64)) == "oracle"
    assert ops.minplus_dispatch((64, 64)) == "oracle"
    # mismatched leading batch dims always fall back to the oracle
    assert ops.minplus_dispatch((2, 256, 256), (3, 256, 256)) == "oracle"
    # forcing overrides the size threshold, not the structure
    assert ops.minplus_dispatch((3, 8, 8), use_pallas=True) == "pallas_batched"
    assert ops.minplus_dispatch((256, 256), use_pallas=False) == "oracle"


def test_closure_traces_through_batched_kernel():
    """A batched closure actually reaches the batched Pallas kernel (counted
    at trace time via the dispatch tally)."""
    rng = np.random.default_rng(3)
    w = jnp.asarray(_inf_sparse(rng, (3, 40, 40)))
    ops.reset_dispatch_counts()
    got = ops.minplus_closure(w, use_pallas=True)
    assert ops.dispatch_counts().get("pallas_batched", 0) >= 1
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.minplus_closure_ref(w)),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# Flash attention kernels (kernels/flash.py)
# ---------------------------------------------------------------------------

def _attn_ref(q, k, v, scale):
    s = jnp.einsum("bsd,btd->bst", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    n = q.shape[1]
    m = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    s = jnp.where(m[None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bst,btd->bsd", p.astype(q.dtype), v)


@pytest.mark.parametrize("bh,S,d,dv,bq,bk", [
    (2, 256, 64, 64, 128, 128), (3, 512, 128, 96, 128, 256),
    (1, 256, 192, 128, 64, 64), (2, 128, 64, 64, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_forward_matches_ref(bh, S, d, dv, bq, bk, dtype):
    import math
    from repro.kernels.flash import flash_attention_bhsd
    ks = jax.random.split(jax.random.PRNGKey(S + d), 3)
    q = jax.random.normal(ks[0], (bh, S, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (bh, S, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (bh, S, dv), jnp.float32).astype(dtype)
    scale = 1 / math.sqrt(d)
    out = flash_attention_bhsd(q, k, v, scale=scale, bq=min(bq, S),
                               bk=min(bk, S), interpret=True)
    want = _attn_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                     v.astype(jnp.float32), scale)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), atol=tol, rtol=tol)


def test_flash_grads_match_autodiff():
    import math
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    bh, S, d, dv = 2, 256, 64, 64
    q = jax.random.normal(ks[0], (bh, S, d))
    k = jax.random.normal(ks[1], (bh, S, d))
    v = jax.random.normal(ks[2], (bh, S, dv))
    g = jax.random.normal(ks[3], (bh, S, dv))
    scale = 1 / math.sqrt(d)
    f = lambda *a: jnp.sum(ops.flash_attention(*a, scale=scale, bq=128,
                                               bk=128) * g)
    fr = lambda *a: jnp.sum(_attn_ref(*a, scale) * g)
    va, ga = jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)
    vb, gb = jax.value_and_grad(fr, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(va, vb, rtol=1e-4)
    for a, b in zip(ga, gb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-3)


def test_flash_logsumexp_output():
    import math
    from repro.kernels.flash import flash_fwd_lse
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    bh, S, d = 1, 128, 64
    q = jax.random.normal(ks[0], (bh, S, d))
    k = jax.random.normal(ks[1], (bh, S, d))
    v = jax.random.normal(ks[2], (bh, S, d))
    scale = 1 / math.sqrt(d)
    o, lse = flash_fwd_lse(q, k, v, scale=scale, bq=64, bk=64,
                           interpret=True)
    s = jnp.einsum("bsd,btd->bst", q, k) * scale
    mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(mask[None], s, -jnp.inf)
    want = jax.scipy.special.logsumexp(s, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_dispatch_counts_raises_under_active_trace():
    """dispatch_counts() is a host-side, trace-time tally: reading it while
    a trace is in flight would mix finished and in-progress tracings (and a
    traced reader would bake the stale snapshot into the compiled program),
    so the guarded reader refuses instead of silently over/under-counting."""
    ops.reset_dispatch_counts()
    seen = []

    @jax.jit
    def traced(x):
        with pytest.raises(RuntimeError, match="active jax trace"):
            ops.dispatch_counts()
        seen.append(True)
        return ops.minplus_matmul(x, x)

    w = jnp.zeros((4, 4), jnp.float32)
    traced(w)
    assert seen  # the traced body really ran (and really raised)
    # outside the trace the tally reads fine and saw the traced call above
    assert ops.dispatch_counts().get("oracle", 0) == 1
