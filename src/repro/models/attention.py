"""Attention: GQA with RoPE, sliding windows, gemma2 logit soft-capping.

Four execution paths:
  * ``splash_attention`` — train/prefill on a TPU: JAX's bundled Pallas
                        splash kernel (forward, dq and dkv kernels), GQA heads
                        grouped in the kernel and fully masked blocks skipped,
                        so no score tile goes through HBM. ``attn_kernel_takes``
                        is the rule of ``cfg.attn_impl='auto'`` that picks it.
  * ``mha_chunked``   — memory-efficient blockwise attention (online softmax)
                        in pure jnp; train/prefill where the kernel's rule
                        does not hold, and always under ``attn_impl='xla'``.
                        Block bounds are static per query-block, so causal and
                        sliding-window structure statically skips KV blocks
                        (no masked-out FLOPs outside the diagonal band).
  * ``decode_attention`` — single-token attention over a (ring-buffered) KV
                        cache; reductions stay sharded over the cache's seq
                        axis under GSPMD.
  * Pallas flash attention (``repro.kernels.flash_attention``) — TPU target,
    selected with ``cfg.attn_impl='pallas'`` (interpret mode on CPU).

Weights are kept 3-D ``(d_model, heads, head_dim)`` so the head axis has a
clean mesh sharding.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from .layers import apply_rope, _dense_init

NEG_INF = -1e30


def attn_init(rng, d_model: int, n_heads: int, n_kv: int, head_dim: int):
    kq, kk, kv, ko = jax.random.split(rng, 4)
    return {
        "wq": _dense_init(kq, (d_model, n_heads, head_dim)),
        "wk": _dense_init(kk, (d_model, n_kv, head_dim)),
        "wv": _dense_init(kv, (d_model, n_kv, head_dim)),
        "wo": _dense_init(ko, (n_heads, head_dim, d_model), in_axis=1),
    }


def _repeat_kv(k: jnp.ndarray, n_heads: int) -> jnp.ndarray:
    """(B,T,Kv,hd) -> (B,T,H,hd) by repeating each kv head H/Kv times."""
    B, T, Kv, hd = k.shape
    if Kv == n_heads:
        return k
    rep = n_heads // Kv
    return jnp.broadcast_to(k[:, :, :, None, :], (B, T, Kv, rep, hd)).reshape(B, T, n_heads, hd)


def naive_attention(q, k, v, *, causal=True, window=0, softcap_val=0.0,
                    q_offset=0):
    """O(S^2)-memory reference. q:(B,S,H,hd) k,v:(B,T,Kv,hd)."""
    B, S, H, hd = q.shape
    T = k.shape[1]
    k = _repeat_kv(k, H)
    v = _repeat_kv(v, H)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s / math.sqrt(hd)
    if softcap_val:
        s = softcap_val * jnp.tanh(s / softcap_val)
    qpos = jnp.arange(S) + q_offset
    kpos = jnp.arange(T)
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window:
        mask &= (qpos[:, None] - kpos[None, :]) < window
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return out


def _block_scores(qb, kb_r, qpos, kp_, *, scale, causal, window,
                  softcap_val):
    """Masked scores (B,H,qb,kb) of a query block against a KV block, and
    the soft-cap's tanh (None without one)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb_r,
                   preferred_element_type=jnp.float32) * scale
    t = None
    if softcap_val:
        t = jnp.tanh(s / softcap_val)
        s = softcap_val * t
    msk = jnp.ones((qb.shape[1], kb_r.shape[1]), bool)
    if causal:
        msk &= qpos[:, None] >= kp_[None, :]
    if window:
        msk &= (qpos[:, None] - kp_[None, :]) < window
    # an additive (qb,kb) bias: a mask broadcast over batch and heads is
    # what XLA would hoist out of the KV scan and keep for every step
    return s + jnp.where(msk, 0.0, NEG_INF)[None, None], t


def mha_chunked(q, k, v, *, causal=True, window=0, softcap_val=0.0,
                q_block=512, kv_block=512, q_offset=0):
    """Blockwise attention with online softmax; never materialises (S,T).

    A scan over query blocks -> for each, a loop over the KV blocks its
    causal/windowed mask admits (bounds fixed per block at trace time), so
    FLOPs track the band instead of the full square.  The backward pass is
    flash attention's: it keeps only q, k, v, the output and each query's
    log-sum-exp, and recomputes the probabilities block by block.
    """
    B, S, H, hd = q.shape
    T = k.shape[1]
    q_block = min(q_block, S)
    kv_block = min(kv_block, T)
    assert S % q_block == 0 and T % kv_block == 0, (S, q_block, T, kv_block)
    return _mha_chunked(q, k, v, causal, window, softcap_val, q_block,
                        kv_block, q_offset)


def _kv_blocks(S, T, *, causal, window, q_block, kv_block, q_offset):
    """(query blocks, 3) int32: each query block's start and the range of
    KV blocks [lo, hi) that its causal/windowed mask admits."""
    rows = []
    for qs in range(0, S, q_block):
        q_abs_lo, q_abs_hi = q_offset + qs, q_offset + qs + q_block
        lo = max(0, q_abs_lo - window + 1) if window else 0
        hi = min(T, q_abs_hi) if causal else T
        rows.append((qs, lo // kv_block, min(-(-hi // kv_block),
                                              T // kv_block)))
    return jnp.asarray(rows, jnp.int32)


def _mha_forward(q, k, v, causal, window, softcap_val, q_block, kv_block,
                 q_offset):
    """The attention output and each query block's log-sum-exp of its
    scores, (query blocks, B, H, qb)."""
    B, S, H, hd = q.shape
    T = k.shape[1]
    masks = dict(scale=1.0 / math.sqrt(hd), causal=causal, window=window,
                 softcap_val=softcap_val)
    blocks = _kv_blocks(S, T, causal=causal, window=window, q_block=q_block,
                        kv_block=kv_block, q_offset=q_offset)

    def qblock(_, b):
        qs, lo, hi = b[0], b[1], b[2]
        qb = jax.lax.dynamic_slice_in_dim(q, qs, q_block, axis=1)
        qpos = jnp.arange(q_block, dtype=jnp.int32) + q_offset + qs

        def body(bi, carry):
            m, l, acc = carry
            # dynamic-slice the KV blocks out of the full tensors (closed
            # over) instead of feeding stacked slices through the loop —
            # this avoids materialising staggered copies of K/V.
            start = bi * kv_block
            kb_ = jax.lax.dynamic_slice_in_dim(k, start, kv_block, axis=1)
            vb_ = jax.lax.dynamic_slice_in_dim(v, start, kv_block, axis=1)
            kp_ = start + jnp.arange(kv_block, dtype=jnp.int32)
            kb_r = _repeat_kv(kb_, H)
            vb_r = _repeat_kv(vb_, H)
            s, _ = _block_scores(qb, kb_r, qpos, kp_, **masks)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vb_r.dtype), vb_r,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return m_new, l_new, acc_new

        m0 = jnp.full((B, H, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, q_block), jnp.float32)
        a0 = jnp.zeros((B, H, q_block, hd), jnp.float32)
        m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, a0))
        l = jnp.maximum(l, 1e-30)
        ob = (acc / l[..., None]).swapaxes(1, 2)        # (B,qb,H,hd)
        return None, (ob.astype(q.dtype), m + jnp.log(l))

    _, (obs, lses) = jax.lax.scan(qblock, None, blocks)
    return obs.swapaxes(0, 1).reshape(B, S, H, hd), lses


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _mha_chunked(q, k, v, causal, window, softcap_val, q_block, kv_block,
                 q_offset):
    return _mha_forward(q, k, v, causal, window, softcap_val, q_block,
                        kv_block, q_offset)[0]


def _mha_chunked_fwd(q, k, v, causal, window, softcap_val, q_block,
                     kv_block, q_offset):
    out, lses = _mha_forward(q, k, v, causal, window, softcap_val, q_block,
                             kv_block, q_offset)
    return out, (q, k, v, out, lses)


def _mha_chunked_bwd(causal, window, softcap_val, q_block, kv_block,
                     q_offset, res, g):
    """Flash attention's backward: per query block, a loop over its KV
    blocks recomputes the probabilities from the saved log-sum-exp and
    accumulates dq, and dk, dv in f32 over the whole sequence."""
    q, k, v, out, lses = res
    B, S, H, hd = q.shape
    T, Kv = k.shape[1], k.shape[2]
    rep = H // Kv
    scale = 1.0 / math.sqrt(hd)
    masks = dict(scale=scale, causal=causal, window=window,
                 softcap_val=softcap_val)
    blocks = _kv_blocks(S, T, causal=causal, window=window, q_block=q_block,
                        kv_block=kv_block, q_offset=q_offset)
    f32 = jnp.float32

    def fold(x):                                        # heads -> kv heads
        return x.reshape(B, kv_block, Kv, rep, hd).sum(3)

    def qblock(carry, xs):
        dq, dk, dv = carry
        (qs, lo, hi), lse = (xs[0][0], xs[0][1], xs[0][2]), xs[1]
        qb = jax.lax.dynamic_slice_in_dim(q, qs, q_block, axis=1)
        gb = jax.lax.dynamic_slice_in_dim(g, qs, q_block, axis=1)
        ob = jax.lax.dynamic_slice_in_dim(out, qs, q_block, axis=1)
        qpos = jnp.arange(q_block, dtype=jnp.int32) + q_offset + qs
        # rowsum(dO * O): the softmax's backward term, (B,H,qb)
        delta = jnp.sum(gb.astype(f32) * ob.astype(f32),
                        axis=-1).swapaxes(1, 2)

        def body(bi, c):
            dqb, dk, dv = c
            start = bi * kv_block
            kb_ = jax.lax.dynamic_slice_in_dim(k, start, kv_block, axis=1)
            vb_ = jax.lax.dynamic_slice_in_dim(v, start, kv_block, axis=1)
            kp_ = start + jnp.arange(kv_block, dtype=jnp.int32)
            kb_r = _repeat_kv(kb_, H)
            vb_r = _repeat_kv(vb_, H)
            s, t = _block_scores(qb, kb_r, qpos, kp_, **masks)
            p = jnp.exp(s - lse[..., None])
            dp = jnp.einsum("bqhd,bkhd->bhqk", gb, vb_r,
                            preferred_element_type=f32)
            ds = p * (dp - delta[..., None])
            if t is not None:
                ds = ds * (1.0 - t * t)
            ds = (ds * scale).astype(q.dtype)
            dqb = dqb + jnp.einsum("bhqk,bkhd->bqhd", ds, kb_r,
                                   preferred_element_type=f32)
            dk_j = fold(jnp.einsum("bhqk,bqhd->bkhd", ds, qb,
                                   preferred_element_type=f32))
            dv_j = fold(jnp.einsum("bhqk,bqhd->bkhd", p.astype(g.dtype), gb,
                                   preferred_element_type=f32))
            dk = jax.lax.dynamic_update_slice_in_dim(
                dk, jax.lax.dynamic_slice_in_dim(dk, start, kv_block, 1)
                + dk_j, start, axis=1)
            dv = jax.lax.dynamic_update_slice_in_dim(
                dv, jax.lax.dynamic_slice_in_dim(dv, start, kv_block, 1)
                + dv_j, start, axis=1)
            return dqb, dk, dv

        dq0 = jnp.zeros((B, q_block, H, hd), f32)
        dqb, dk, dv = jax.lax.fori_loop(lo, hi, body, (dq0, dk, dv))
        dq = jax.lax.dynamic_update_slice_in_dim(dq, dqb.astype(q.dtype), qs,
                                                 axis=1)
        return (dq, dk, dv), None

    init = (jnp.zeros(q.shape, q.dtype), jnp.zeros((B, T, Kv, hd), f32),
            jnp.zeros((B, T, Kv, hd), f32))
    (dq, dk, dv), _ = jax.lax.scan(qblock, init, (blocks, lses))
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


_mha_chunked.defvjp(_mha_chunked_fwd, _mha_chunked_bwd)


# ---------------------------------------------------------------------------
# Splash attention (jax.experimental.pallas.ops.tpu.splash_attention)
# ---------------------------------------------------------------------------

# every block of the forward, dq and dkv kernels: mha_chunked's own tiling
SPLASH_BLOCK = 512
LANES = 128


def platform_of(x) -> str:
    """The platform a concrete array lives on; the default backend for a
    traced value (or a host array), which is where it will run."""
    if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
        return next(iter(x.devices())).platform
    return jax.default_backend()


def attn_kernel_takes(impl: str, platform: str, seq_len: int, kv_len: int,
                      head_dim: int, *, cached: bool = False) -> bool:
    """The rule of ``attn_impl='auto'``: train/prefill attention runs in the
    splash kernel on a TPU, with no decode cache, as many keys as queries,
    the sequence a multiple of the kernel's block and the head a multiple
    of the lanes; anything else runs ``mha_chunked``."""
    return (impl == "auto" and platform == "tpu" and not cached
            and seq_len == kv_len and seq_len % SPLASH_BLOCK == 0
            and head_dim % LANES == 0)


@functools.lru_cache(maxsize=None)
def _splash_kernel(seq_len: int, window: int, heads: int, softcap: float,
                   block: int, interpret: bool):
    """One kernel (and its mask info) per shape, mask and tiling: causal,
    or ``q - k < window`` and causal; built once, outside any trace."""
    from jax.experimental.pallas.ops.tpu import splash_attention as sa
    b = block
    blocks = sa.BlockSizes(block_q=b, block_kv=b, block_kv_compute=b,
                           block_q_dkv=b, block_kv_dkv=b,
                           block_kv_dkv_compute=b, block_q_dq=b, block_kv_dq=b)
    shape = (seq_len, seq_len)
    mask = (sa.LocalMask(shape, window_size=(window - 1, 0), offset=0)
            if window else sa.CausalMask(shape))
    with jax.ensure_compile_time_eval():
        return sa.make_splash_mha(
            sa.MultiHeadMask([mask] * heads), block_sizes=blocks,
            attn_logits_soft_cap=softcap or None, head_shards=1,
            q_seq_shards=1, interpret=interpret)


def splash_attention(q, k, v, *, window=0, softcap_val=0.0,
                     interpret=False):
    """Causal (``window`` > 0: sliding-window) attention in the splash
    kernel. q:(B,S,H,hd) k,v:(B,S,Kv,hd), each sequence head-major under a
    vmap over B.  The kernel does not scale its scores: q comes already
    multiplied by 1/sqrt(hd) (``attn_apply`` does it in RoPE's f32
    product, before q's one rounding to bf16)."""
    B, S, H, hd = q.shape
    kernel = _splash_kernel(S, window, H, float(softcap_val), SPLASH_BLOCK,
                            interpret)
    heads = lambda t: t.swapaxes(1, 2)           # (B,S,h,hd) <-> (B,h,S,hd)
    return heads(jax.vmap(kernel)(heads(q), heads(k), heads(v)))


# ---------------------------------------------------------------------------
# KV cache (supports ring buffers for sliding-window archs)
# ---------------------------------------------------------------------------

def init_kv_cache(batch: int, cache_len: int, n_kv: int, head_dim: int,
                  dtype=jnp.bfloat16):
    return {
        "k": jnp.zeros((batch, cache_len, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, cache_len, n_kv, head_dim), dtype),
        # global position held by each slot; -1 = empty
        "slot_pos": jnp.full((cache_len,), -1, jnp.int32),
    }


def cache_write(cache, k_new, v_new, pos):
    """Write one step (B,1,Kv,hd) at global position ``pos`` (traced scalar)."""
    C = cache["k"].shape[1]
    idx = jnp.mod(pos, C)
    k = jax.lax.dynamic_update_slice(cache["k"], k_new, (0, idx, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new, (0, idx, 0, 0))
    sp = jax.lax.dynamic_update_slice(cache["slot_pos"],
                                      pos[None].astype(jnp.int32), (idx,))
    return {"k": k, "v": v, "slot_pos": sp}


def decode_attention(q, cache, *, window=0, softcap_val=0.0, cur_pos=None):
    """q: (B,1,H,hd) attends over the cache. Mask from slot positions, so the
    same code serves full caches and ring buffers."""
    B, S1, H, hd = q.shape
    k, v, slot_pos = cache["k"], cache["v"], cache["slot_pos"]
    kr = _repeat_kv(k, H)
    vr = _repeat_kv(v, H)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    if softcap_val:
        s = softcap_val * jnp.tanh(s / softcap_val)
    valid = slot_pos >= 0
    if cur_pos is not None:
        valid &= slot_pos <= cur_pos
        if window:
            valid &= (cur_pos - slot_pos) < window
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vr.dtype), vr)
    return out


# ---------------------------------------------------------------------------
# Full attention block application
# ---------------------------------------------------------------------------

def attn_apply(params, x, *, cfg, window: int = 0, rope_theta=None,
               yarn=None, cache=None, cur_pos=None,
               impl: Optional[str] = None):
    """x: (B,S,D). If ``cache`` is provided, runs one decode step and returns
    (out, new_cache); else runs train/prefill and returns (out, (k,v)).
    ``window``: 0 = full attention (callers resolve a layer period's
    windows); ``yarn``: the layer's YaRN scaling of RoPE, if any."""
    dt = x.dtype
    theta = rope_theta if rope_theta is not None else cfg.rope_theta
    B, S, D = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dt))

    if cache is not None:
        pos = jnp.broadcast_to(cur_pos, (B, S))
        q = apply_rope(q, pos, theta, yarn)
        k = apply_rope(k, pos, theta, yarn)
        new_cache = cache_write(cache, k, v, cur_pos)
        out = decode_attention(q, new_cache, window=window,
                               softcap_val=cfg.attn_softcap, cur_pos=cur_pos)
        o = jnp.einsum("bshk,hkd->bsd", out.astype(dt), params["wo"].astype(dt))
        return o, new_cache

    impl = impl or cfg.attn_impl
    platform = platform_of(x)
    kernel = attn_kernel_takes(impl, platform, S, S, q.shape[-1])
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    # the kernel takes q scaled: RoPE's f32 product scales it, so q is
    # rounded once, as mha_chunked's unscaled q is
    q = apply_rope(q, pos, theta, yarn,
                   scale=1.0 / math.sqrt(q.shape[-1]) if kernel else 1.0)
    k = apply_rope(k, pos, theta, yarn)
    from repro.parallel import constrain_qkv
    q, k, v = constrain_qkv(q, k, v)
    if kernel:
        out = splash_attention(q, k, v, window=window,
                               softcap_val=cfg.attn_softcap,
                               interpret=platform != "tpu")
    elif impl in ("auto", "xla"):
        out = mha_chunked(q, k, v, causal=True, window=window,
                          softcap_val=cfg.attn_softcap)
    elif impl in ("pallas", "pallas_interpret"):
        from repro.kernels import flash_attention as fa
        out = fa.flash_attention(q, k, v, causal=True, window=window,
                                 softcap=cfg.attn_softcap,
                                 interpret=(impl == "pallas_interpret"))
    else:
        raise ValueError(impl)
    # bf16 partial sums: the head contraction is sharded over `model`, so the
    # cross-shard psum moves bf16 instead of f32 partials
    o = jnp.einsum("bshk,hkd->bsd", out.astype(dt), params["wo"].astype(dt),
                   preferred_element_type=dt)
    return o, (k, v)
