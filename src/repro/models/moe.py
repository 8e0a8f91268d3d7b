"""Mixture-of-Experts FFN with GShard-style capacity dispatch.

Tokens are grouped, routed top-k, and dispatched to per-expert capacity
buffers via one-hot einsums (the standard TPU-friendly formulation: dense
matmuls, no data-dependent shapes, drops overflow tokens). Expert compute is
``E x capacity`` tokens = ``top_k * capacity_factor * N`` — active-param
FLOPs, not ``E x N``.

Sharding: expert-parallel over the ``model`` mesh axis when ``E`` divides the
axis (phi3.5: 16 experts), else tensor-parallel over expert ``d_ff``
(mixtral: 8 experts).

``moe_share_apply`` is one chip's share of an expert-parallel layer: it
holds a contiguous slice of the experts, routes every token over all of
them, and computes its own experts' part of the result for every token
routed to them, with no capacity and no dropped token.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .layers import _dense_init


def moe_init(rng, d_model: int, d_ff: int, n_experts: int,
             n_held: Optional[int] = None):
    """Router over ``n_experts``; weights of ``n_held`` experts (all by
    default)."""
    kr, k1, k2, k3 = jax.random.split(rng, 4)
    e = n_experts if n_held is None else n_held
    return {
        "router": _dense_init(kr, (d_model, n_experts)),
        "wi_gate": _dense_init(k1, (e, d_model, d_ff), in_axis=1),
        "wi_up": _dense_init(k2, (e, d_model, d_ff), in_axis=1),
        "wo": _dense_init(k3, (e, d_ff, d_model), in_axis=1),
    }


def moe_apply(params, x, *, top_k: int, capacity_factor: float = 1.25,
              group_size: int = 2048):
    """x: (B,S,D) -> (out, aux_loss)."""
    dt = x.dtype
    B, S, D = x.shape
    N = B * S
    xf = x.reshape(N, D)
    E = params["router"].shape[1]
    G = min(group_size, N)
    assert N % G == 0, (N, G)
    ng = N // G
    xg = xf.reshape(ng, G, D)

    logits = jnp.einsum("gsd,de->gse", xg, params["router"].astype(dt),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                       # (g,s,E)
    gate_w, gate_idx = jax.lax.top_k(probs, top_k)                # (g,s,k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    cap = int(G * top_k * capacity_factor / E)
    cap = max(8, -(-cap // 8) * 8)
    cap = min(cap, G)

    oh = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)           # (g,s,k,E)
    # choice-major priority: all 1st choices before any 2nd choice
    ohp = oh.transpose(0, 2, 1, 3).reshape(ng, top_k * G, E)
    pos = jnp.cumsum(ohp, axis=1) * ohp - 1.0                     # slot id or -1
    keep = (pos >= 0) & (pos < cap)
    slot = jax.nn.one_hot(pos.clip(0, cap - 1), cap, dtype=dt)
    slot = slot * keep[..., None].astype(dt)                      # (g,kS,E,C)
    slot = jax.lax.stop_gradient(
        slot.reshape(ng, top_k, G, E, cap).transpose(0, 2, 1, 3, 4))

    dispatch = slot.sum(2)                                        # (g,s,E,C)
    combine = jnp.einsum("gskec,gsk->gsec", slot, gate_w.astype(dt))

    xe = jnp.einsum("gsec,gsd->gecd", dispatch, xg)               # (g,E,C,D)
    h_gate = jnp.einsum("gecd,edf->gecf", xe, params["wi_gate"].astype(dt))
    h_up = jnp.einsum("gecd,edf->gecf", xe, params["wi_up"].astype(dt))
    h = jax.nn.silu(h_gate) * h_up
    ye = jnp.einsum("gecf,efd->gecd", h, params["wo"].astype(dt))
    y = jnp.einsum("gsec,gecd->gsd", combine, ye)

    # Switch-style load-balance aux loss
    me = probs.mean(axis=(0, 1))                                  # (E,)
    ce = oh[:, :, 0, :].mean(axis=(0, 1))                         # 1st-choice load
    aux = E * jnp.sum(me * ce)
    return y.reshape(B, S, D), aux


@jax.custom_vjp
def _take_rows(x, idx, back):
    """``x[idx]``, whose backward gathers instead of scattering: row ``i``
    of ``x`` was taken at the positions ``back[i]`` (a (len(x), m) table),
    so its cotangent is the sum of theirs (a scatter-add over duplicate
    rows serialises on the TPU)."""
    return x[idx]


def _take_rows_fwd(x, idx, back):
    return x[idx], back


def _take_rows_bwd(back, g):
    dx = jnp.sum(g[back], axis=1, dtype=jnp.float32).astype(g.dtype)
    return dx, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def moe_share_apply(params, x, *, top_k: int, expert_offset: int = 0):
    """x: (B,S,D) -> (this share's part of the layer's output, aux=0).

    Softmax over all the router's experts in f32, top-``top_k``,
    renormalised over those ``top_k``.  The (token, choice) pairs whose
    expert is held here (``expert_offset`` onward, as many as
    ``params["wi_gate"]`` holds) are sorted by expert; the held experts'
    SwiGLU runs as grouped products over the sorted rows, and each row
    goes back to its token weighted by its gate.  The row buffer has room
    for every pair, so no token is dropped; pairs of absent experts add
    nothing."""
    dt = x.dtype
    B, S, D = x.shape
    N = B * S
    xf = x.reshape(N, D)
    E_held = params["wi_gate"].shape[0]
    logits = jnp.dot(xf, params["router"].astype(dt),
                     preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                       # (N,E)
    gate_w, gate_idx = jax.lax.top_k(probs, top_k)                # (N,k)
    gate_w = gate_w / gate_w.sum(-1, keepdims=True)
    local = gate_idx - expert_offset
    held = (local >= 0) & (local < E_held)
    # absent experts sort last, under the key E_held
    key = jnp.where(held, local, E_held).reshape(N * top_k)
    order = jnp.argsort(key, stable=True)                         # sorted -> pair
    inv = jnp.argsort(order)                                      # pair -> sorted
    group_sizes = jnp.sum(key[:, None] == jnp.arange(E_held), axis=0,
                          dtype=jnp.int32)
    # rows past the held groups: what the grouped products leave there
    # (forward and backward) is unspecified, so they are zeroed each time
    valid = (jnp.arange(N * top_k) < jnp.sum(group_sizes))[:, None]
    rows = jnp.where(valid, _take_rows(xf, order // top_k,
                                       inv.reshape(N, top_k)), 0)
    gate = jnp.where(valid, jax.lax.ragged_dot(
        rows, params["wi_gate"].astype(dt), group_sizes), 0)
    up = jnp.where(valid, jax.lax.ragged_dot(
        rows, params["wi_up"].astype(dt), group_sizes), 0)
    y = jnp.where(valid, jax.lax.ragged_dot(
        jax.nn.silu(gate) * up, params["wo"].astype(dt), group_sizes), 0)
    # back to (token, choice) order; each token's held choices summed in
    # f32, weighted by their gates
    y = _take_rows(y, inv, order[:, None]).reshape(N, top_k, D)
    w = jnp.where(held, gate_w, 0.0).astype(dt)
    out = jnp.einsum("nkd,nk->nd", y, w, preferred_element_type=jnp.float32)
    return out.astype(dt).reshape(B, S, D), jnp.zeros((), jnp.float32)
