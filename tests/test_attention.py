"""Chunked (flash-style) XLA attention vs naive reference; decode-path
consistency (prefill + serve_step == forward over extended sequence)."""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.models.attention import (decode_attention, init_kv_cache,
                                    cache_write, mha_chunked, naive_attention)


def _qkv(rng, B=2, S=128, H=4, Kv=2, D=32, dtype=jnp.float32):
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Kv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Kv, D), dtype)
    return q, k, v


@pytest.mark.parametrize("causal,window,cap", [
    (True, 0, 0.0), (True, 32, 0.0), (True, 0, 50.0),
    (False, 0, 0.0), (True, 64, 30.0)])
def test_chunked_matches_naive(causal, window, cap):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    out = mha_chunked(q, k, v, causal=causal, window=window, softcap_val=cap,
                      q_block=32, kv_block=32)
    ref = naive_attention(q, k, v, causal=causal, window=window,
                          softcap_val=cap)
    assert jnp.max(jnp.abs(out - ref)) < 2e-5


@pytest.mark.parametrize("causal,window,cap,kv", [
    (True, 0, 0.0, 2), (True, 32, 0.0, 2), (True, 0, 50.0, 1),
    (False, 0, 0.0, 4), (True, 48, 30.0, 2)])
def test_chunked_gradients_match_naive(causal, window, cap, kv):
    """The flash backward (log-sum-exp residual, probabilities recomputed
    per KV block) gives the gradients that autodiff of the O(S^2)
    reference gives, GQA heads folded back onto their KV heads."""
    q, k, v = _qkv(jax.random.PRNGKey(4), Kv=kv)
    w = jax.random.normal(jax.random.PRNGKey(5), q.shape)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v, causal=causal, window=window,
                          softcap_val=cap) * w)
    chunked = functools.partial(mha_chunked, q_block=32, kv_block=16)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(functools.partial(loss, chunked), (0, 1, 2))(q, k, v)
        want = jax.grad(functools.partial(loss, naive_attention),
                        (0, 1, 2))(q, k, v)
    # f32 throughout: the two differ in summation order only
    for a, b in zip(got, want):
        assert jnp.max(jnp.abs(a - b)) < 1e-4 * jnp.max(jnp.abs(b)) + 1e-5


@pytest.mark.parametrize("qb,kb", [(16, 64), (64, 16), (128, 128)])
def test_chunked_block_size_invariance(qb, kb):
    q, k, v = _qkv(jax.random.PRNGKey(1))
    a = mha_chunked(q, k, v, q_block=qb, kv_block=kb)
    b = mha_chunked(q, k, v, q_block=128, kv_block=128)
    assert jnp.max(jnp.abs(a - b)) < 2e-5


def test_chunked_bf16():
    q, k, v = _qkv(jax.random.PRNGKey(2), dtype=jnp.bfloat16)
    out = mha_chunked(q, k, v, q_block=32, kv_block=32)
    ref = naive_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32))
    assert jnp.max(jnp.abs(out.astype(jnp.float32) - ref)) < 3e-2


def test_mqa_and_mha_head_grouping():
    # Kv == H (MHA) and Kv == 1 (MQA)
    for Kv in (1, 4):
        q, k, v = _qkv(jax.random.PRNGKey(3), Kv=Kv)
        out = mha_chunked(q, k, v, q_block=32, kv_block=32)
        ref = naive_attention(q, k, v)
        assert jnp.max(jnp.abs(out - ref)) < 2e-5


def test_decode_matches_full_attention():
    """Serve one new token over a cache built from the first S-1 tokens;
    compare against full attention over all S tokens."""
    B, S, H, Kv, D = 2, 33, 4, 2, 16
    q, k, v = _qkv(jax.random.PRNGKey(4), B=B, S=S, H=H, Kv=Kv, D=D)
    full = naive_attention(q, k, v, causal=True)
    cache = init_kv_cache(B, S, Kv, D, dtype=jnp.float32)
    for t in range(S):
        cache = cache_write(cache, k[:, t:t + 1], v[:, t:t + 1], jnp.int32(t))
    out = decode_attention(q[:, -1:], cache, cur_pos=jnp.int32(S - 1))
    assert jnp.max(jnp.abs(out[:, 0] - full[:, -1])) < 2e-5


def test_decode_ring_buffer_window():
    """Window attention decode through a ring cache == windowed full attn."""
    B, S, H, Kv, D, W = 1, 40, 2, 2, 16, 8
    q, k, v = _qkv(jax.random.PRNGKey(5), B=B, S=S, H=H, Kv=Kv, D=D)
    full = naive_attention(q, k, v, causal=True, window=W)
    cache = init_kv_cache(B, W, Kv, D, dtype=jnp.float32)   # ring of W slots
    for t in range(S):
        cache = cache_write(cache, k[:, t:t + 1], v[:, t:t + 1], jnp.int32(t))
    out = decode_attention(q[:, -1:], cache, window=W, cur_pos=jnp.int32(S - 1))
    assert jnp.max(jnp.abs(out[:, 0] - full[:, -1])) < 2e-5
