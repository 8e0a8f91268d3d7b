"""Fused (staleness-)weighted federated averaging Pallas kernel.

The aggregation server's hot loop is HBM-bound: read W worker models, write
one. A naive tree-map issues W reads + W-1 adds per leaf with intermediate
round trips; this kernel streams a (W, BN) tile through VMEM and emits the
weighted sum in a single pass — per-byte traffic = (W+1)/(2W-1) of the naive
chain and no intermediate materialisation.

Block: (W, 512) f32 tiles (W workers is small: 2..32), 128-lane aligned.

The same fused contraction serves the massive-scale cohort row window
(``flatbuf.FlatServerState.merge_window``): there W is the WINDOW
capacity (O(cohort), not the population), each in-flight update owns a
claimed row, and the per-update weight is scattered to its row index in
the weight vector — stale/free rows sit zeroed at weight 0, which
contributes nothing to the dot_general.  No kernel change: lane->worker
indirection lives entirely in the weight vector.

Sharded variants (``*_sharded``): the same kernels over a 1-D aggregation
server mesh.  The packed (W, N) layout puts every worker's lane for a given
parameter on ONE device when N is sharded, so the staleness-weighted
W-reduce runs per-shard with no cross-device traffic; the only collective
in the whole merge pipeline is the optional ``all_gather`` that
re-materialises a replicated result (``gather=True`` — unpack/eval
consumers).  Pallas calls do not auto-partition under GSPMD, hence the
explicit ``shard_map``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

# f32 contractions at full f32 precision: at the default, a TPU may run an
# f32 matmul as a single bf16 pass, rounding every merged weight to bf16
HIGHEST = jax.lax.Precision.HIGHEST

def _agg_kernel(w_ref, x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)        # (W, BN)
    w = w_ref[...].astype(jnp.float32)        # (1, W)
    o_ref[...] = jax.lax.dot_general(
        w, x, (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _mix_kernel(w_ref, x_ref, s_ref, o_ref):
    """o = w[0]*server + w[1:] @ stacked, one VMEM pass per (W+1, BN) tile."""
    x = x_ref[...].astype(jnp.float32)        # (W, BN)
    s = s_ref[...].astype(jnp.float32)        # (1, BN)
    w = w_ref[...].astype(jnp.float32)        # (1, W+1)
    acc = jax.lax.dot_general(
        w[:, 1:], x, (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)
    o_ref[...] = (w[:, 0:1] * s + acc).astype(o_ref.dtype)


def fedavg_agg_flat(stacked: jnp.ndarray, weights: jnp.ndarray,
                    block_n: int = 512, interpret: bool = False) -> jnp.ndarray:
    """stacked: (W, N) worker models (flattened); weights: (W,) normalised.
    Returns (N,) = weights @ stacked."""
    W, N = stacked.shape
    block_n = min(block_n, N)
    pad = (-N) % block_n
    if pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, pad)))
    Np = N + pad
    out = pl.pallas_call(
        _agg_kernel,
        grid=(Np // block_n,),
        in_specs=[
            pl.BlockSpec((1, W), lambda i: (0, 0)),
            pl.BlockSpec((W, block_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Np), stacked.dtype),
        interpret=interpret,
    )(weights.reshape(1, W), stacked)
    return out[0, :N]


def fedavg_mix_flat(stacked: jnp.ndarray, weights: jnp.ndarray,
                    server: jnp.ndarray, server_scale,
                    block_n: int = 512, interpret: bool = False) -> jnp.ndarray:
    """Fused aggregate + server mixing in one HBM pass.

    Returns ``server_scale * server + weights @ stacked``:

      * ``server_scale = 1 - alpha`` with ``weights = alpha * w_hat`` is the
        FedAsync server-mixing damping fused with the weighted sum;
      * ``server_scale = 1`` with signed weights is the delta-accumulate form
        (``server + sum_i w_i * delta_i``) used by ``async_delta`` mode.

    stacked: (W, N); weights: (W,) already scaled; server: (N,).
    The server row streams through the same VMEM tile as the worker rows, so
    per-byte traffic is (W+2)/(2W+1) of the unfused aggregate-then-mix chain
    and no (N,) intermediate is materialised. When N is already a multiple of
    ``block_n`` the server buffer aliases the output (in-place update).
    """
    W, N = stacked.shape
    block_n = min(block_n, N)
    pad = (-N) % block_n
    wvec = jnp.concatenate([
        jnp.asarray(server_scale, jnp.float32).reshape(1),
        weights.astype(jnp.float32).reshape(W)]).reshape(1, W + 1)
    server = server.reshape(1, N)
    if pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, pad)))
        server = jnp.pad(server, ((0, 0), (0, pad)))
    Np = N + pad
    out = pl.pallas_call(
        _mix_kernel,
        grid=(Np // block_n,),
        in_specs=[
            pl.BlockSpec((1, W + 1), lambda i: (0, 0)),
            pl.BlockSpec((W, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Np), server.dtype),
        input_output_aliases={} if pad else {2: 0},
        interpret=interpret,
    )(wvec, stacked, server)
    return out[0, :N]


def fedavg_delta_flat(server: jnp.ndarray, deltas: jnp.ndarray,
                      weights: jnp.ndarray, block_n: int = 512,
                      interpret: bool = False) -> jnp.ndarray:
    """Delta-accumulate variant: ``server + weights @ deltas`` (async_delta
    mode / FedBuff-style additive composition), same fused single pass."""
    return fedavg_mix_flat(deltas, weights, server, 1.0,
                           block_n=block_n, interpret=interpret)


# ---------------------------------------------------------------------------
# Server-optimizer step: one fused elementwise pass over the packed buffers
# ---------------------------------------------------------------------------
# The merge produced `merged` (the FedAvg-style aggregate, already
# alpha-mixed); the server optimizer transforms the pseudo-gradient
# d = merged - prev into the actual server step in the SAME packed space:
#
#   m' = am * m + bm * d                      (momentum / drift state)
#   v' = av * v + bv * d*d                    (adam second moment)
#   new = prev + cd * d + lr * m'             (momentum form, adam=False)
#   new = prev + lr * m' / (sqrt(v') + tau)   (adam form,     adam=True)
#
# One scalar vector covers FedAvgM (am=mu, bm=1, cd=0), FedDyn-style drift
# (am=1, bm=1, cd=1, lr=gamma) and FedAdam (am=b1, bm=1-b1, av=b2,
# bv=1-b2) — see core/server_opt.py for the optimizer table.  Everything
# is elementwise along N, so the sharded variant needs no collective.

def _opt_mom_kernel(sc_ref, prev_ref, mg_ref, m_ref, o_new_ref, o_m_ref):
    sc = sc_ref[...].astype(jnp.float32)          # (1, 4): am, bm, cd, lr
    prev = prev_ref[...].astype(jnp.float32)      # (1, BN)
    d = mg_ref[...].astype(jnp.float32) - prev
    m = sc[0, 0] * m_ref[...].astype(jnp.float32) + sc[0, 1] * d
    o_m_ref[...] = m.astype(o_m_ref.dtype)
    o_new_ref[...] = (prev + sc[0, 2] * d
                      + sc[0, 3] * m).astype(o_new_ref.dtype)


def _opt_adam_kernel(sc_ref, prev_ref, mg_ref, m_ref, v_ref,
                     o_new_ref, o_m_ref, o_v_ref):
    sc = sc_ref[...].astype(jnp.float32)          # (1, 6): b1, b2, lr, tau
    prev = prev_ref[...].astype(jnp.float32)
    d = mg_ref[...].astype(jnp.float32) - prev
    m = sc[0, 0] * m_ref[...].astype(jnp.float32) + (1.0 - sc[0, 0]) * d
    v = sc[0, 1] * v_ref[...].astype(jnp.float32) + (1.0 - sc[0, 1]) * d * d
    o_m_ref[...] = m.astype(o_m_ref.dtype)
    o_v_ref[...] = v.astype(o_v_ref.dtype)
    o_new_ref[...] = (prev + sc[0, 2] * m
                      / (jnp.sqrt(v) + sc[0, 3])).astype(o_new_ref.dtype)


def _pad_vecs(vecs, pad):
    return [jnp.pad(v.reshape(1, -1), ((0, 0), (0, pad))) if pad
            else v.reshape(1, -1) for v in vecs]


def server_opt_step_flat(prev, merged, m, v, scalars, *, adam: bool,
                         block_n: int = 512, interpret: bool = False):
    """Fused optimizer step over (N,) packed f32 buffers.

    ``scalars``: (4,) ``[am, bm, cd, lr]`` for the momentum form or (6,)
    ``[b1, b2, lr, tau, 0, 0]`` for the adam form.  Returns
    ``(new, m', v')`` with ``v'`` None when ``adam`` is False."""
    N = prev.shape[-1]
    block_n = min(block_n, N)
    pad = (-N) % block_n
    Np = N + pad
    if adam:
        sc = scalars.astype(jnp.float32).reshape(1, 6)
        prev_p, mg_p, m_p, v_p = _pad_vecs((prev, merged, m, v), pad)
        outs = pl.pallas_call(
            _opt_adam_kernel,
            grid=(Np // block_n,),
            in_specs=[pl.BlockSpec((1, 6), lambda i: (0, 0))]
            + [pl.BlockSpec((1, block_n), lambda i: (0, i))] * 4,
            out_specs=[pl.BlockSpec((1, block_n), lambda i: (0, i))] * 3,
            out_shape=[jax.ShapeDtypeStruct((1, Np), jnp.float32)] * 3,
            interpret=interpret,
        )(sc, prev_p, mg_p, m_p, v_p)
        new, m_out, v_out = (o[0, :N] for o in outs)
        return new, m_out, v_out
    sc = scalars.astype(jnp.float32).reshape(1, 4)
    prev_p, mg_p, m_p = _pad_vecs((prev, merged, m), pad)
    outs = pl.pallas_call(
        _opt_mom_kernel,
        grid=(Np // block_n,),
        in_specs=[pl.BlockSpec((1, 4), lambda i: (0, 0))]
        + [pl.BlockSpec((1, block_n), lambda i: (0, i))] * 3,
        out_specs=[pl.BlockSpec((1, block_n), lambda i: (0, i))] * 2,
        out_shape=[jax.ShapeDtypeStruct((1, Np), jnp.float32)] * 2,
        interpret=interpret,
    )(sc, prev_p, mg_p, m_p)
    new, m_out = (o[0, :N] for o in outs)
    return new, m_out, None


# ---------------------------------------------------------------------------
# Sharded variants: shard_map over a 1-D server mesh, N-sharded buffers
# ---------------------------------------------------------------------------

def _check_shardable(N: int, mesh, axis: str) -> int:
    D = mesh.shape[axis]
    if N % D:
        raise ValueError(f"flat buffer width {N} not divisible by the "
                         f"{D}-device '{axis}' mesh axis — pack with a "
                         f"mesh-aware ParamBundle (pads N to divisibility)")
    return D


def fedavg_mix_flat_sharded(stacked: jnp.ndarray, weights: jnp.ndarray,
                            server: jnp.ndarray, server_scale, *, mesh,
                            axis: str = "agg", block_n: int = 512,
                            interpret: bool = False,
                            gather: bool = False) -> jnp.ndarray:
    """``server_scale * server + weights @ stacked`` over a 1-D server mesh.

    ``stacked`` (W, N) is sharded ``P(None, axis)`` and ``server`` (N,)
    ``P(axis)``; each device streams its local (W, N/D) block through the
    fused single-pass kernel, so the staleness-weighted sum + alpha-mix run
    entirely per-shard — the packed layout keeps every worker's lane of a
    parameter on one device and the cross-device reduce collapses to the
    one optional collective (``gather=True``: an ``all_gather`` along
    ``axis`` that returns the replicated (N,) result; default keeps the
    output sharded as the next round's server buffer)."""
    W, N = stacked.shape
    _check_shardable(N, mesh, axis)
    wvec = jnp.concatenate([
        jnp.asarray(server_scale, jnp.float32).reshape(1),
        weights.astype(jnp.float32).reshape(W)])

    def local(wv, x, s):
        out = fedavg_mix_flat(x, wv[1:], s, wv[0], block_n=block_n,
                              interpret=interpret)
        if gather:
            out = jax.lax.all_gather(out, axis, tiled=True)
        return out

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(), P(None, axis), P(axis)),
                         out_specs=P() if gather else P(axis),
                         check_vma=False)(wvec, stacked, server)


def fedavg_agg_flat_sharded(stacked: jnp.ndarray, weights: jnp.ndarray, *,
                            mesh, axis: str = "agg", block_n: int = 512,
                            interpret: bool = False,
                            gather: bool = False) -> jnp.ndarray:
    """Sharded ``weights @ stacked`` (no server term — the alpha>=1
    replace-on-aggregate path must not read the server buffer; see
    ``flatbuf.fused_weighted_sum``), same per-shard kernel launch."""
    _, N = stacked.shape
    _check_shardable(N, mesh, axis)

    def local(w, x):
        out = fedavg_agg_flat(x, w, block_n=block_n, interpret=interpret)
        if gather:
            out = jax.lax.all_gather(out, axis, tiled=True)
        return out

    return jax.shard_map(local, mesh=mesh, in_specs=(P(), P(None, axis)),
                         out_specs=P() if gather else P(axis),
                         check_vma=False)(weights, stacked)


def server_opt_step_flat_sharded(prev, merged, m, v, scalars, *,
                                 adam: bool, mesh, axis: str = "agg",
                                 block_n: int = 512,
                                 interpret: bool = False):
    """Sharded fused optimizer step: every buffer is ``P(axis)`` along N
    and the update is elementwise, so each device runs the single-pass
    kernel on its own (N/D,) slice — no collective at all (the optimizer
    never couples coordinates across shards)."""
    N = prev.shape[-1]
    _check_shardable(N, mesh, axis)
    if adam:
        def local(sc, p, mg, mm, vv):
            return server_opt_step_flat(p, mg, mm, vv, sc, adam=True,
                                        block_n=block_n,
                                        interpret=interpret)
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(axis), P(axis), P(axis), P(axis)),
            out_specs=(P(axis), P(axis), P(axis)),
            check_vma=False)(scalars, prev, merged, m, v)

    def local_mom(sc, p, mg, mm):
        new, mo, _ = server_opt_step_flat(p, mg, mm, None, sc, adam=False,
                                          block_n=block_n,
                                          interpret=interpret)
        return new, mo
    new, mo = jax.shard_map(local_mom, mesh=mesh,
                            in_specs=(P(), P(axis), P(axis), P(axis)),
                            out_specs=(P(axis), P(axis)),
                            check_vma=False)(scalars, prev, merged, m)
    return new, mo, None
