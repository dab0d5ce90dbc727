"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
all in interpret mode (CPU container; TPU is the deploy target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rwkv6_chunk import wkv6_chunked
from repro.kernels.ssd_chunk import ssd_chunked
from repro.kernels.tropical_route import tropical_route

KEY = jax.random.PRNGKey(42)


def tol(dt):
    return 2e-2 if dt == jnp.bfloat16 else 2e-4


@pytest.mark.parametrize("B,S,Hq,Hkv,D", [
    (2, 64, 4, 2, 32),
    (1, 128, 8, 2, 64),
    (2, 96, 3, 1, 16),     # MQA, ragged heads
    (1, 256, 2, 2, 128),   # MHA, MXU-width head
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(B, S, Hq, Hkv, D, dtype, causal):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    out = flash_attention(q, k, v, causal=causal, blk_q=32, blk_k=32,
                          interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), atol=tol(dtype))


@pytest.mark.parametrize("B,S,Hq,Hkv,D", [
    (2, 64, 4, 2, 32),
    (3, 256, 8, 1, 64),
    (1, 128, 5, 5, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(B, S, Hq, Hkv, D, dtype):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, Hq, D), dtype)
    ck = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    cv = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    kv_len = jax.random.randint(ks[3], (B,), 1, S + 1)
    out = decode_attention(q, ck, cv, kv_len, blk_k=32, interpret=True)
    want = ref.decode_attention_ref(q, ck, cv, kv_len)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), atol=tol(dtype))


@pytest.mark.parametrize("R,P,L,segs", [
    (8, 64, 12, (3, 4, 6)),
    (16, 128, 36, (3, 6, 9)),
    (8, 32, 8, (2, 4)),
])
def test_tropical_route(R, P, L, segs):
    rng = np.random.default_rng(0)
    starts, ends = [], []
    for _ in range(P):
        s = int(rng.choice(segs))
        st = int(rng.integers(0, L // s)) * s
        starts.append(st)
        ends.append(min(st + s, L))
    starts = np.array(starts, np.int32)
    ends = np.array(ends, np.int32)
    costs = rng.uniform(1, 500, (R, P)).astype(np.float32)
    costs[rng.random((R, P)) < 0.3] = 3.0e38
    dist, pred = tropical_route(jnp.array(starts), jnp.array(ends),
                                jnp.array(costs), total_layers=L,
                                blk_r=8, interpret=True)
    rd, rp = ref.tropical_route_ref(starts, ends, costs, L)
    finite = np.isfinite(rd) & (rd < 1e38)
    np.testing.assert_allclose(np.where(finite, np.asarray(dist), 0),
                               np.where(finite, rd, 0), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(pred), rp)


@pytest.mark.parametrize("B,S,H,K,chunk", [
    (2, 64, 2, 16, 16),
    (1, 128, 4, 32, 32),
    (2, 96, 3, 8, 32),
])
def test_wkv6_chunked(B, S, H, K, chunk):
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, S, H, K))
    k = jax.random.normal(ks[1], (B, S, H, K))
    v = jax.random.normal(ks[2], (B, S, H, K))
    lw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, K)) - 2.0)
    u = 0.3 * jax.random.normal(ks[4], (H, K))
    s0 = jnp.zeros((B, H, K, K))
    y, s = wkv6_chunked(r, k, v, lw, u, s0, chunk=chunk, interpret=True)
    yr, sr = ref.wkv6_ref(r, k, v, lw, u, s0)
    np.testing.assert_allclose(y, yr, atol=5e-4)
    np.testing.assert_allclose(s, sr, atol=5e-4)


def test_wkv6_nonzero_initial_state():
    ks = jax.random.split(KEY, 6)
    B, S, H, K = 1, 32, 2, 8
    r, k, v = (jax.random.normal(ks[i], (B, S, H, K)) for i in range(3))
    lw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, K)) - 2.0)
    u = 0.1 * jax.random.normal(ks[4], (H, K))
    s0 = jax.random.normal(ks[5], (B, H, K, K))
    y, s = wkv6_chunked(r, k, v, lw, u, s0, chunk=8, interpret=True)
    yr, sr = ref.wkv6_ref(r, k, v, lw, u, s0)
    np.testing.assert_allclose(y, yr, atol=5e-4)
    np.testing.assert_allclose(s, sr, atol=5e-4)


@pytest.mark.parametrize("B,S,H,P,N,G,chunk", [
    (2, 64, 2, 16, 8, 1, 16),
    (1, 128, 4, 32, 16, 1, 32),
    (1, 64, 4, 16, 8, 2, 16),
    (2, 96, 8, 16, 8, 2, 32),
])
def test_ssd_chunked(B, S, H, P, N, G, chunk):
    """The Pallas scan (interpret mode), the jnp chunked scan and the
    per-position recurrence agree, with B and C in G groups and a
    carried initial state."""
    from repro.models.mamba2 import ssd_chunked as ssd_jnp
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    la = -jnp.exp(jax.random.normal(ks[2], (B, S, H)) - 1.0) * dt
    Bm = jax.random.normal(ks[3], (B, S, G, N))
    Cm = jax.random.normal(ks[4], (B, S, G, N))
    h0 = 0.1 * jax.random.normal(ks[5], (B, H, N, P))
    dx = x * dt[..., None]
    y, h = ssd_chunked(dx, la, Bm, Cm, h0, chunk=chunk, interpret=True)
    yr, hr = ref.ssd_ref(dx, la, Bm, Cm, h0)
    yj, hj = ssd_jnp(x, dt, la, Bm, Cm, h0, chunk)
    for got in ((y, h), (yj, hj)):
        np.testing.assert_allclose(got[0], yr, atol=5e-4)
        np.testing.assert_allclose(got[1], hr, atol=5e-4)


def test_ssd_pads_to_the_chunk():
    """``mamba2.ssd`` over a length that is no multiple of the chunk
    equals the recurrence: padded steps leave the state as it is."""
    from repro.models.mamba2 import ssd
    B, S, H, P, N, G = 1, 21, 4, 8, 8, 2
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    la = -jnp.exp(jax.random.normal(ks[2], (B, S, H))) * dt
    Bm = jax.random.normal(ks[3], (B, S, G, N))
    Cm = jax.random.normal(ks[4], (B, S, G, N))
    h0 = jnp.zeros((B, H, N, P))
    y, h = ssd(x, dt, la, Bm, Cm, h0, chunk=8)
    yr, hr = ref.ssd_ref(x * dt[..., None], la, Bm, Cm, h0)
    assert y.shape == x.shape
    np.testing.assert_allclose(y, yr, atol=5e-4)
    np.testing.assert_allclose(h, hr, atol=5e-4)


def test_wkv6_strong_decay_no_overflow():
    """Overflow-safety: decay near 0 (log-decay very negative)."""
    B, S, H, K = 1, 64, 1, 8
    ks = jax.random.split(KEY, 3)
    r, k, v = (jax.random.normal(ks[i], (B, S, H, K)) for i in range(3))
    lw = jnp.full((B, S, H, K), -20.0)       # w = e^-20: brutal decay
    u = jnp.zeros((H, K))
    s0 = jnp.zeros((B, H, K, K))
    y, s = wkv6_chunked(r, k, v, lw, u, s0, chunk=32, interpret=True)
    assert bool(jnp.all(jnp.isfinite(y))) and bool(jnp.all(jnp.isfinite(s)))
    yr, _ = ref.wkv6_ref(r, k, v, lw, u, s0)
    np.testing.assert_allclose(y, yr, atol=5e-4)
