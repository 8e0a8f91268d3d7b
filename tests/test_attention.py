"""Chunked (flash-style) XLA attention and the splash kernel (interpret
mode) vs naive reference; the rule that picks the kernel; decode-path
consistency (prefill + serve_step == forward over extended sequence)."""
import functools
import math

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ModelConfig
from repro.models import attention
from repro.models.attention import (decode_attention, init_kv_cache,
                                    cache_write, mha_chunked, naive_attention,
                                    splash_attention)


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


# --- the splash kernel (interpret mode on the CPU) ----------------------------

@pytest.mark.parametrize("S,window", [
    (1024, 0), (1024, 512), (1024, 300), (2048, 0), (2048, 1000)])
def test_splash_matches_naive_and_chunked(S, window):
    """Forward and the q, k, v gradients of the kernel at its 512 blocks,
    8 query heads over 2 KV heads of 128, causal or windowed (300 and
    1,000 cut blocks): f32 throughout, so only summation order differs."""
    q, k, v = _qkv(jax.random.PRNGKey(6), B=1, S=S, H=8, Kv=2, D=128)
    w = jax.random.normal(jax.random.PRNGKey(7), q.shape)
    scaled = lambda q, k, v, **kw: splash_attention(
        q / math.sqrt(q.shape[-1]), k, v, interpret=True, **kw)
    fns = {
        "splash": scaled,
        "naive": functools.partial(naive_attention, causal=True),
        "chunked": functools.partial(mha_chunked, causal=True),
    }

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v, window=window) * w)

    with jax.default_matmul_precision("highest"):
        outs = {n: f(q, k, v, window=window) for n, f in fns.items()}
        grads = {n: jax.grad(functools.partial(loss, f), (0, 1, 2))(q, k, v)
                 for n, f in fns.items()}
    for ref in ("naive", "chunked"):
        assert jnp.max(jnp.abs(outs["splash"] - outs[ref])) < 2e-5
        for a, b in zip(grads["splash"], grads[ref]):
            assert jnp.max(jnp.abs(a - b)) < 1e-4 * jnp.max(jnp.abs(b)) + 1e-5


@pytest.mark.parametrize("window,cap", [(0, 0.0), (300, 50.0)])
def test_splash_bf16_matches_f32_reference(window, cap):
    """The training path's operands: bf16 q, k, v (q scaled in f32 before
    its cast), f32 accumulation; soft-capped logits through the kernel's
    own cap."""
    q, k, v = _qkv(jax.random.PRNGKey(8), B=2, S=1024, H=8, Kv=2, D=128)
    bf = lambda t: t.astype(jnp.bfloat16)
    out = splash_attention(bf(q / math.sqrt(128)), bf(k), bf(v),
                           window=window, softcap_val=cap, interpret=True)
    f32 = lambda t: bf(t).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref = naive_attention(f32(q), f32(k), f32(v), causal=True,
                              window=window, softcap_val=cap)
        chunked = mha_chunked(bf(q), bf(k), bf(v), causal=True, window=window,
                              softcap_val=cap)
    assert out.dtype == jnp.bfloat16
    err = jnp.max(jnp.abs(out.astype(jnp.float32) - ref))
    assert err < 3e-2
    # no further from the reference than the chunked path at bf16
    assert err <= 2 * jnp.max(jnp.abs(chunked.astype(jnp.float32) - ref))


@pytest.mark.parametrize("impl,platform,S,T,hd,cached,takes", [
    ("auto", "tpu", 8192, 8192, 128, False, True),
    ("auto", "tpu", 1024, 1024, 256, False, True),
    ("auto", "tpu", 1, 8192, 128, True, False),       # a decode cache
    ("auto", "tpu", 1000, 1000, 128, False, False),   # S not a block multiple
    ("auto", "tpu", 1024, 2048, 128, False, False),   # S != T
    ("auto", "tpu", 1024, 1024, 64, False, False),    # head not a lane multiple
    ("auto", "cpu", 1024, 1024, 128, False, False),   # a CPU array
    ("xla", "tpu", 1024, 1024, 128, False, False),    # forced chunked
])
def test_attn_kernel_rule(impl, platform, S, T, hd, cached, takes):
    assert attention.attn_kernel_takes(impl, platform, S, T, hd,
                                       cached=cached) is takes


def test_a_cpu_array_is_on_the_cpu():
    assert attention.platform_of(jnp.zeros((2,))) == "cpu"
    assert attention.platform_of(
        jax.ShapeDtypeStruct((2,), jnp.float32)) == jax.default_backend()


def _attn_setup():
    cfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=64,
                      n_heads=4, n_kv_heads=2, head_dim=128, d_ff=64,
                      vocab_size=11)
    params = attention.attn_init(jax.random.PRNGKey(9), 64, 4, 2, 128)
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 512, 64))
    return cfg, params, x


@pytest.mark.parametrize("window", [0, 200])
def test_attn_apply_auto_follows_the_rule(monkeypatch, window):
    """``attn_impl='auto'`` on the CPU is the chunked path bit for bit;
    with the rule forced, the same call runs the kernel (interpret mode)
    and agrees with it to f32 rounding."""
    cfg, params, x = _attn_setup()
    assert cfg.attn_impl == "auto"
    run = lambda impl: attention.attn_apply(params, x, cfg=cfg, window=window,
                                            impl=impl)[0]
    chunked = run("xla")
    assert jnp.array_equal(run("auto"), chunked)
    seen = []
    monkeypatch.setattr(attention, "attn_kernel_takes",
                        lambda *a, **k: seen.append(a) or True)
    kernel = run("auto")
    assert seen and seen[0][:2] == ("auto", "cpu")
    assert jnp.max(jnp.abs(kernel - chunked)) < 1e-4 * jnp.max(
        jnp.abs(chunked)) + 1e-5
