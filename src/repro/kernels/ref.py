"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# the merge oracles contract at full f32 precision, as the kernels do
HIGHEST = jax.lax.Precision.HIGHEST


def reference_attention(q, k, v, *, causal=True, window=0, softcap=0.0):
    """O(S^2) attention. q:(B,S,H,D); k,v:(B,T,Kv,D)."""
    B, S, H, D = q.shape
    T, Kv = k.shape[1], k.shape[2]
    rep = H // Kv
    kr = jnp.repeat(k, rep, axis=2)
    vr = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) / math.sqrt(D)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    qpos = jnp.arange(S)
    kpos = jnp.arange(T)
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window:
        mask &= (qpos[:, None] - kpos[None, :]) < window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vr.astype(jnp.float32)).astype(q.dtype)


def reference_fedavg(stacked, weights):
    """(W,N) x (W,) -> (N,)."""
    return jnp.einsum("wn,w->n", stacked.astype(jnp.float32),
                      weights.astype(jnp.float32),
                      precision=HIGHEST).astype(stacked.dtype)


def reference_fedavg_sharded(stacked, weights, server, server_scale,
                             n_shards: int):
    """Oracle for the shard_map'ed merge: slice N into ``n_shards`` equal
    ranges, run the mix per shard, concatenate.  The packed (W, N) layout
    keeps the W-reduce shard-local, so this must equal the global
    ``server_scale * server + weights @ stacked`` — any cross-shard
    dependency in the sharded kernel would break the equality."""
    W, N = stacked.shape
    assert N % n_shards == 0, (N, n_shards)
    S = N // n_shards
    outs = []
    for d in range(n_shards):
        sl = slice(d * S, (d + 1) * S)
        outs.append(server_scale * server[sl].astype(jnp.float32)
                    + jnp.einsum("wn,w->n", stacked[:, sl].astype(jnp.float32),
                                 weights.astype(jnp.float32),
                                 precision=HIGHEST))
    return jnp.concatenate(outs).astype(server.dtype)


def reference_server_opt(prev, merged, m, v, scalars, *, adam: bool):
    """Oracle for the fused server-optimizer step (``server_opt_step_flat``).

    ``d = merged - prev`` is the pseudo-gradient the FedAvg merge implies;
    the optimizer turns it into the actual server step:

      momentum form (``adam=False``, scalars = [am, bm, cd, lr]):
        m' = am*m + bm*d;  new = prev + cd*d + lr*m'
      adam form (``adam=True``, scalars = [b1, b2, lr, tau, 0, 0]):
        m' = b1*m + (1-b1)*d;  v' = b2*v + (1-b2)*d^2
        new = prev + lr * m' / (sqrt(v') + tau)

    Returns ``(new, m', v')`` with ``v'`` None in the momentum form."""
    f32 = jnp.float32
    prev, merged, m = prev.astype(f32), merged.astype(f32), m.astype(f32)
    sc = jnp.asarray(scalars, f32)
    d = merged - prev
    if adam:
        mo = sc[0] * m + (1.0 - sc[0]) * d
        vo = sc[1] * v.astype(f32) + (1.0 - sc[1]) * d * d
        return prev + sc[2] * mo / (jnp.sqrt(vo) + sc[3]), mo, vo
    mo = sc[0] * m + sc[1] * d
    return prev + sc[2] * d + sc[3] * mo, mo, None


def reference_server_opt_sharded(prev, merged, m, v, scalars, *,
                                 adam: bool, n_shards: int):
    """Oracle for the shard_map'ed optimizer step: slice N into equal
    ranges, step per shard, concatenate.  The update is elementwise, so
    this must equal the global step exactly — any cross-shard coupling in
    the sharded kernel would break the equality."""
    N = prev.shape[-1]
    assert N % n_shards == 0, (N, n_shards)
    S = N // n_shards
    news, mos, vos = [], [], []
    for dshard in range(n_shards):
        sl = slice(dshard * S, (dshard + 1) * S)
        new, mo, vo = reference_server_opt(
            prev[sl], merged[sl], m[sl], None if v is None else v[sl],
            scalars, adam=adam)
        news.append(new)
        mos.append(mo)
        vos.append(vo)
    return (jnp.concatenate(news), jnp.concatenate(mos),
            None if vos[0] is None else jnp.concatenate(vos))


def reference_topk_quant_encode(x, thresh, scale):
    """Oracle for the fused topk-threshold + int8 quantise encode: entries
    with |x| >= thresh are linearly quantised to int8 (zero elsewhere); the
    residual is the full reconstruction error (error-feedback memory).
    x: (N,) f32; thresh, scale: scalars. Returns (q int8, residual f32)."""
    x = x.astype(jnp.float32)
    mask = jnp.abs(x) >= thresh
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0)
    q = jnp.where(mask, q, 0.0).astype(jnp.int8)
    recon = q.astype(jnp.float32) * scale
    return q, x - recon


def reference_dequant_add(q, scale, base):
    """Oracle for the fused dequantise + delta-apply decode:
    ``base + q * scale``. q: (N,) int8; base: (N,) f32; scale: scalar."""
    return base.astype(jnp.float32) + q.astype(jnp.float32) * scale


def reference_wkv(r, k, v, w, u):
    """Sequential WKV recurrence (the ground truth the chunked forms must
    match). r,k,v,w: (B,S,H,K); u: (H,K)."""
    f32 = jnp.float32
    B, S, H, K = r.shape
    r, k, v, w = (t.astype(f32) for t in (r, k, v, w))
    u = u.astype(f32)

    def step(state, xs):
        rt, kt, vt, wt = xs                      # (B,H,K)
        kv = jnp.einsum("bhk,bhv->bhkv", kt, vt)
        y = jnp.einsum("bhk,bhkv->bhv", rt, state + u[None, :, :, None] * kv)
        state = state * wt[..., None] + kv
        return state, y

    xs = tuple(t.transpose(1, 0, 2, 3) for t in (r, k, v, w))
    s0 = jnp.zeros((B, H, K, K), f32)
    _, ys = jax.lax.scan(step, s0, xs)
    return ys.transpose(1, 0, 2, 3).astype(r.dtype)
