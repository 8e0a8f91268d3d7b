"""Model assembly for all six architecture families, plus the jit-able
train / prefill / serve step functions.

Layer stacks are ``lax.scan``s over vmapped-init (stacked) block params so the
block body compiles once regardless of depth; a layer period (gemma2's
local/global pairs, mellum2's three windowed layers and one full) scans over
*periods* so each position keeps a static window and its RoPE (and the
chunked attention keeps static KV-block skipping). zamba2 scans over groups of
``shared_attn_every`` mamba2 blocks followed by one application of a single
shared attention block.

``lm_sgd_train`` is a federated worker's local training of a language model
(full-batch SGD on f32 master weights, bf16 compute), and ``lm_hits`` its
evaluation.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.parallel import constrain_act

from . import attention as attn_mod
from . import mamba2 as mamba_mod
from . import moe as moe_mod
from . import rwkv6 as rwkv_mod
from .layers import (COMPUTE_DTYPE, chunked_ce_loss, embed, embed_init,
                     glu_mlp, glu_mlp_init, rmsnorm, rmsnorm_init, softcap)

# ---------------------------------------------------------------------------
# Block init
# ---------------------------------------------------------------------------

def _attn_block_init(rng, cfg):
    k1, k2 = jax.random.split(rng)
    p = {
        "ln1": rmsnorm_init(cfg.d_model),
        "ln2": rmsnorm_init(cfg.d_model),
        "attn": attn_mod.attn_init(k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                   cfg.hd),
    }
    if cfg.is_moe:
        p["moe"] = moe_mod.moe_init(k2, cfg.d_model, cfg.d_ff, cfg.n_experts,
                                    cfg.n_experts_here)
    else:
        p["mlp"] = glu_mlp_init(k2, cfg.d_model, cfg.d_ff)
    if cfg.post_block_norm:
        p["post_ln1"] = rmsnorm_init(cfg.d_model)
        p["post_ln2"] = rmsnorm_init(cfg.d_model)
    return p


def _rwkv_block_init(rng, cfg):
    return {
        "ln1": rmsnorm_init(cfg.d_model),
        "ln2": rmsnorm_init(cfg.d_model),
        "rwkv": rwkv_mod.rwkv6_init(rng, cfg.d_model, cfg.d_ff, cfg.n_heads,
                                    cfg.ssm_head_dim),
    }


def _mamba_block_init(rng, cfg):
    return {
        "ln": rmsnorm_init(cfg.d_model),
        "mamba": mamba_mod.mamba2_init(rng, cfg.d_model, expand=cfg.ssm_expand,
                                       head_dim=cfg.ssm_head_dim,
                                       n_state=cfg.ssm_state),
    }


def _stack_init(rng, init_fn, n):
    return jax.vmap(init_fn)(jax.random.split(rng, n))


def init_params(rng, cfg):
    ke, kb, ks = jax.random.split(rng, 3)
    params = {"embed": embed_init(ke, cfg.vocab_size, cfg.d_model),
              "final_norm": rmsnorm_init(cfg.d_model)}
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(jax.random.fold_in(ke, 1),
                                       cfg.vocab_size, cfg.d_model)
    if cfg.block_type == "attn":
        init1 = lambda k: _attn_block_init(k, cfg)
        P = len(cfg.period)
        if P:
            assert cfg.n_layers % P == 0
            period = lambda k: jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[_attn_block_init(kk, cfg) for kk in jax.random.split(k, P)])
            params["blocks"] = _stack_init(kb, period, cfg.n_layers // P)
        else:
            params["blocks"] = _stack_init(kb, init1, cfg.n_layers)
    elif cfg.block_type == "rwkv6":
        params["blocks"] = _stack_init(kb, lambda k: _rwkv_block_init(k, cfg),
                                       cfg.n_layers)
    elif cfg.block_type == "mamba2":
        n_groups = cfg.n_shared_attn_applications()
        per = cfg.shared_attn_every
        trailing = cfg.n_layers - n_groups * (per + 1)
        grp = lambda k: _stack_init(k, lambda kk: _mamba_block_init(kk, cfg), per)
        params["blocks"] = _stack_init(kb, grp, n_groups)        # (G, per, ...)
        k1, k2 = jax.random.split(ks)
        params["shared_attn"] = _attn_block_init(k1, cfg)
        if trailing:
            params["tail"] = _stack_init(k2, lambda kk: _mamba_block_init(kk, cfg),
                                         trailing)
    else:
        raise ValueError(cfg.block_type)
    # Params live in bf16 (compute dtype): collectives that move weights
    # (FSDP gathers) move half the bytes. The fp32 master copy lives in the
    # optimizer state.
    return jax.tree.map(
        lambda p: p.astype(COMPUTE_DTYPE) if jnp.issubdtype(p.dtype, jnp.floating)
        else p, params)


# ---------------------------------------------------------------------------
# Block apply
# ---------------------------------------------------------------------------

def _attn_block_apply(p, x, cfg, *, window, cache=None, cur_pos=None):
    h = rmsnorm(p["ln1"], x)
    # YaRN, where the config has it, is the full layers' RoPE
    yarn = cfg.rope_yarn if window == 0 else None
    a, kv = attn_mod.attn_apply(p["attn"], h, cfg=cfg, window=window,
                                yarn=yarn, cache=cache, cur_pos=cur_pos)
    if cfg.post_block_norm:
        a = rmsnorm(p["post_ln1"], a)
    x = x + a
    h = rmsnorm(p["ln2"], x)
    aux = jnp.zeros((), jnp.float32)
    if cfg.experts_held:
        f, aux = moe_mod.moe_share_apply(p["moe"], h, top_k=cfg.top_k,
                                         expert_offset=cfg.expert_offset)
    elif cfg.is_moe:
        f, aux = moe_mod.moe_apply(p["moe"], h, top_k=cfg.top_k,
                                   capacity_factor=cfg.capacity_factor)
    else:
        act = "gelu" if cfg.post_block_norm else "silu"
        f = glu_mlp(p["mlp"], h, act=act)
    if cfg.post_block_norm:
        f = rmsnorm(p["post_ln2"], f)
    return x + f, aux, kv


def _rwkv_block_apply(p, x, cfg, state=None):
    st_tm = state["tm"] if state is not None else None
    a, new_tm = rwkv_mod.time_mix(p["rwkv"]["tm"], rmsnorm(p["ln1"], x),
                                  cfg.n_heads, cfg.ssm_head_dim, st_tm)
    x = x + a
    st_cm = state["cm"] if state is not None else None
    f, new_cm = rwkv_mod.channel_mix(p["rwkv"]["cm"], rmsnorm(p["ln2"], x), st_cm)
    return x + f, {"tm": new_tm, "cm": new_cm}


def _mamba_block_apply(p, x, cfg, state=None):
    a, new_state = mamba_mod.mamba2_apply(
        p["mamba"], rmsnorm(p["ln"], x), expand=cfg.ssm_expand,
        head_dim=cfg.ssm_head_dim, n_state=cfg.ssm_state, state=state)
    return x + a, new_state


# ---------------------------------------------------------------------------
# Cache construction helpers
# ---------------------------------------------------------------------------

def _kv_from_full(k, v, cache_len):
    """Turn full-sequence K/V (B,S,Kv,hd) into a decode cache of ``cache_len``
    slots: ring layout when cache_len < S (matching pos % C addressing),
    zero-padded headroom (slot_pos = -1) when cache_len > S."""
    S = k.shape[1]
    if cache_len < S:
        k, v = k[:, -cache_len:], v[:, -cache_len:]
        slot_pos = jnp.arange(S - cache_len, S, dtype=jnp.int32)
        # ring address: slot index = pos % C; since S % C == 0 this slice is
        # already ring-aligned (pos % C == j for j-th element)
        return {"k": k, "v": v, "slot_pos": slot_pos}
    if cache_len > S:
        pad = cache_len - S
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        slot_pos = jnp.concatenate([jnp.arange(S, dtype=jnp.int32),
                                    jnp.full((pad,), -1, jnp.int32)])
        return {"k": k, "v": v, "slot_pos": slot_pos}
    return {"k": k, "v": v, "slot_pos": jnp.arange(S, dtype=jnp.int32)}


def init_decode_state(cfg, batch: int, context_len: int, dtype=COMPUTE_DTYPE):
    """Zeroed decode state pytree (shapes only matter for the dry-run)."""
    C = cfg.kv_cache_len(context_len)

    def kv(n):
        return {
            "k": jnp.zeros((n, batch, C, cfg.n_kv_heads, cfg.hd), dtype),
            "v": jnp.zeros((n, batch, C, cfg.n_kv_heads, cfg.hd), dtype),
            "slot_pos": jnp.zeros((n, C), jnp.int32),
        }

    if cfg.block_type == "attn":
        P = len(cfg.period)
        if P:
            L = cfg.n_layers // P
            return {"kv": jax.tree.map(
                lambda z: z.reshape((L, P) + z.shape[1:]), kv(cfg.n_layers))}
        return {"kv": kv(cfg.n_layers)}
    if cfg.block_type == "rwkv6":
        L, D, H, K = cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.ssm_head_dim
        return {
            "tm": {"shift": jnp.zeros((L, batch, 1, D), dtype),
                   "wkv": jnp.zeros((L, batch, H, K, K), jnp.float32)},
            "cm": {"shift": jnp.zeros((L, batch, 1, D), dtype)},
        }
    if cfg.block_type == "mamba2":
        G = cfg.n_shared_attn_applications()
        per = cfg.shared_attn_every
        trailing = cfg.n_layers - G * (per + 1)
        d_in = cfg.ssm_expand * cfg.d_model
        nh = d_in // cfg.ssm_head_dim

        def mst(*lead):
            return {"conv_x": jnp.zeros(lead + (batch, mamba_mod.CONV_K - 1, d_in), dtype),
                    "conv_bc": jnp.zeros(lead + (batch, mamba_mod.CONV_K - 1,
                                                 2 * cfg.ssm_state), dtype),
                    "ssm": jnp.zeros(lead + (batch, nh, cfg.ssm_head_dim, cfg.ssm_state),
                                     jnp.float32)}
        st = {"groups": mst(G, per), "shared_kv": kv(G)}
        if trailing:
            st["tail"] = mst(trailing)
        return st
    raise ValueError(cfg.block_type)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def forward(params, cfg, *, tokens=None, embeds=None, state=None, cur_pos=None,
            return_cache=False, cache_len=None):
    """Returns (hidden (B,S,D), aux_loss, new_state_or_None).

    * train:    state=None, return_cache=False
    * prefill:  state=None, return_cache=True  (decode state built from K/V)
    * decode:   state=<pytree>, S==1
    """
    if embeds is not None:
        x = embeds.astype(COMPUTE_DTYPE)
    else:
        x = embed(params["embed"], tokens, scale=cfg.post_block_norm)
    x = constrain_act(x)
    B, S, D = x.shape
    decode = state is not None
    aux_total = jnp.zeros((), jnp.float32)
    new_state = None

    if cfg.block_type == "attn":
        blocks = params["blocks"]
        train = cfg.remat and not decode and not return_cache
        if cfg.period:
            def layer(window):
                def apply(p, x, cache):
                    return _attn_block_apply(p, x, cfg, window=window,
                                             cache=cache, cur_pos=cur_pos)
                # training: one layer's activations live at a time in backward
                return jax.checkpoint(apply) if train else apply

            def period_body(carry, xs):
                x, aux = carry
                x = constrain_act(x)
                p, st = xs
                kvs = []
                for j, window in enumerate(cfg.period):
                    pj = jax.tree.map(lambda t: t[j], p)
                    cj = (jax.tree.map(lambda t: t[j], st["kv"]) if decode
                          else None)
                    x, a, kv = layer(window)(pj, x, cj)
                    aux = aux + a
                    kvs.append(kv)
                stack = lambda *xs: jnp.stack(xs)
                if decode:
                    ys = {"kv": jax.tree.map(stack, *kvs)}
                elif return_cache:
                    C = cache_len or cfg.kv_cache_len(S)
                    ys = {"kv": jax.tree.map(
                        stack, *[_kv_from_full(*kv, C) for kv in kvs])}
                else:
                    ys = 0
                return (x, aux), ys
            body = period_body
        else:
            def blk_body(carry, xs):
                x, aux = carry
                x = constrain_act(x)
                p, st = xs
                c = st["kv"] if decode else None
                x, a, kv = _attn_block_apply(p, x, cfg, window=cfg.window,
                                             cache=c, cur_pos=cur_pos)
                if decode:
                    ys = {"kv": kv}
                elif return_cache:
                    ys = {"kv": _kv_from_full(*kv,
                                              cache_len or cfg.kv_cache_len(S))}
                else:
                    ys = 0
                return (x, aux + a), ys
            body = blk_body
            if train:
                body = jax.checkpoint(body)
        if decode:
            st_xs = state
        else:
            st_xs = {"_": jnp.zeros((jax.tree.leaves(blocks)[0].shape[0],),
                                    jnp.int8)}
        (x, aux_total), caches = jax.lax.scan(body, (x, aux_total), (blocks, st_xs))
        if decode or return_cache:
            new_state = caches

    elif cfg.block_type == "rwkv6":
        def body(carry, xs):
            x = constrain_act(carry)
            p, st = xs
            x, new_st = _rwkv_block_apply(p, x, cfg, state=st if decode else None)
            return x, new_st
        if cfg.remat and not decode and not return_cache:
            body = jax.checkpoint(body)
        dummy = jax.tree.map(lambda t: jnp.zeros((t.shape[0],), jnp.int8),
                             {"_": jax.tree.leaves(params["blocks"])[0]})
        x, states = jax.lax.scan(body, x,
                                 (params["blocks"], state if decode else dummy))
        if decode or return_cache:
            new_state = states

    elif cfg.block_type == "mamba2":
        G = cfg.n_shared_attn_applications()
        per = cfg.shared_attn_every
        trailing = cfg.n_layers - G * (per + 1)
        shared = params["shared_attn"]

        def group_body(carry, xs):
            x = constrain_act(carry)
            p, st = xs

            def inner(c2, xs2):
                x2 = c2
                p2, st2 = xs2
                x2, ns = _mamba_block_apply(p2, x2, cfg,
                                            state=st2 if decode else None)
                return x2, ns
            dummy_in = jax.tree.map(lambda t: jnp.zeros((t.shape[0],), jnp.int8),
                                    {"_": jax.tree.leaves(p)[0]})
            x, mstates = jax.lax.scan(inner, x,
                                      (p, st["groups"] if decode else dummy_in))
            c = st["shared_kv"] if decode else None
            x, _, kv = _attn_block_apply(shared, x, cfg, window=0, cache=c,
                                         cur_pos=cur_pos)
            if decode:
                ys = {"groups": mstates, "shared_kv": kv}
            elif return_cache:
                ys = {"groups": mstates,
                      "shared_kv": _kv_from_full(*kv,
                                                 cache_len or cfg.kv_cache_len(S))}
            else:
                ys = 0
            return x, ys

        if cfg.remat and not decode and not return_cache:
            group_body = jax.checkpoint(group_body)
        grp_params = params["blocks"]
        if decode:
            grp_state = {"groups": state["groups"], "shared_kv": state["shared_kv"]}
        else:
            grp_state = jax.tree.map(lambda t: jnp.zeros((t.shape[0],), jnp.int8),
                                     {"_": jax.tree.leaves(grp_params)[0]})
        x, gstates = jax.lax.scan(group_body, x, (grp_params, grp_state))
        tail_states = None
        if trailing:
            def tail_body(carry, xs):
                x = constrain_act(carry)
                p, st = xs
                x, ns = _mamba_block_apply(p, x, cfg,
                                           state=st if decode else None)
                return x, ns
            if cfg.remat and not decode and not return_cache:
                tail_body = jax.checkpoint(tail_body)
            tdummy = jax.tree.map(lambda t: jnp.zeros((t.shape[0],), jnp.int8),
                                  {"_": jax.tree.leaves(params["tail"])[0]})
            x, tail_states = jax.lax.scan(
                tail_body, x, (params["tail"], state["tail"] if decode else tdummy))
        if decode or return_cache:
            new_state = dict(gstates)
            if trailing:
                new_state["tail"] = tail_states
    else:
        raise ValueError(cfg.block_type)

    x = rmsnorm(params["final_norm"], constrain_act(x))
    return x, aux_total, new_state


# ---------------------------------------------------------------------------
# Steps
# ---------------------------------------------------------------------------

def head_params(params, cfg):
    """The output head: the tied embedding, or the untied ``lm_head``."""
    return params["embed"] if cfg.tie_embeddings else params["lm_head"]


def logits_from_hidden(params, cfg, h):
    table = head_params(params, cfg)["embedding"].astype(h.dtype)
    logits = h @ table.T
    if cfg.final_softcap:
        logits = softcap(logits, cfg.final_softcap)
    return logits


def loss_fn(params, cfg, batch, aux_weight: float = 0.01):
    h, aux, _ = forward(params, cfg,
                        tokens=batch.get("tokens"), embeds=batch.get("embeds"))
    loss = chunked_ce_loss(head_params(params, cfg), h, batch["labels"],
                           chunk=cfg.loss_chunk,
                           final_softcap=cfg.final_softcap,
                           mask=batch.get("mask"))
    return loss + aux_weight * aux, {"ce": loss, "aux": aux}


def train_step(params, opt_state, batch, *, cfg, optimizer, aux_weight=0.01,
               n_microbatch: int = 1, grad_specs=None):
    """One optimizer step; optionally accumulates gradients over
    ``n_microbatch`` sequential microbatches (batch dim split) so backward
    transients scale down by the same factor.

    ``grad_specs``: optional PartitionSpec tree matching ``params`` — pins
    each microbatch gradient to the parameter sharding *before* the fp32
    cast, so the cross-data reduction is a bf16 reduce-scatter instead of a
    full-matrix fp32 all-reduce (see EXPERIMENTS.md §Perf, deepseek cell).
    """
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def pin(g):
        if grad_specs is None:
            return g
        return jax.tree.map(
            lambda gl, sp: jax.lax.with_sharding_constraint(gl, sp),
            g, grad_specs)

    if n_microbatch <= 1:
        (loss, metrics), grads = grad_fn(params, cfg, batch, aux_weight)
        grads = pin(grads)
    else:
        def split(x):
            return x.reshape((n_microbatch, x.shape[0] // n_microbatch)
                             + x.shape[1:])
        ubatches = jax.tree.map(split, batch)
        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def body(acc, ub):
            (l, met), g = grad_fn(params, cfg, ub, aux_weight)
            g = pin(g)
            acc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), acc, g)
            return acc, (l, met)
        grads, (losses, metss) = jax.lax.scan(body, zero, ubatches)
        grads = jax.tree.map(lambda g: g / n_microbatch, grads)
        loss = losses.mean()
        metrics = jax.tree.map(lambda m: m.mean(), metss)
    params, opt_state = optimizer.update(params, grads, opt_state)
    metrics = dict(metrics, loss=loss,
                   grad_norm=optimizer.global_norm(grads))
    return params, opt_state, metrics


def prefill_step(params, batch, *, cfg, max_len=None):
    """``max_len``: total decode horizon — the returned cache gets headroom
    for (max_len - S) further tokens (ring-capped for windowed archs)."""
    cache_len = cfg.kv_cache_len(max_len) if max_len else None
    h, _, state = forward(params, cfg, tokens=batch.get("tokens"),
                          embeds=batch.get("embeds"), return_cache=True,
                          cache_len=cache_len)
    logits = logits_from_hidden(params, cfg, h[:, -1:])
    return logits, state


def serve_step(params, state, tokens, cur_pos, *, cfg, embeds=None):
    """One decode step: tokens (B,1) (or embeds (B,1,D)), cur_pos scalar."""
    h, _, new_state = forward(params, cfg, tokens=tokens, embeds=embeds,
                              state=state, cur_pos=cur_pos)
    logits = logits_from_hidden(params, cfg, h)
    return logits, new_state


# ---------------------------------------------------------------------------
# A federated worker's local training and evaluation
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("epochs", "lr", "cfg"))
def lm_sgd_train(params, tokens, labels, epochs: int, lr: float, cfg):
    """``epochs`` full-batch SGD steps of next-token cross-entropy over a
    worker's shard (``tokens``, ``labels``: (n, S) ids).  The weights stay
    f32 and are cast to bf16 for compute.  Each step's gradient is the mean
    over all n x S tokens at the step's weights, taken ``cfg.microbatches``
    groups of sequences at a time so that one group's activations are
    live, and each group's part is subtracted from the f32 weights as it
    comes (no separate gradient buffer)."""
    n = tokens.shape[0]
    groups = math.gcd(n, max(cfg.microbatches, 1))
    tok = tokens.reshape(groups, n // groups, -1)
    lab = labels.reshape(groups, n // groups, -1)

    def group_loss(pc, t, y):
        h, _, _ = forward(pc, cfg, tokens=t)
        return chunked_ce_loss(head_params(pc, cfg), h, y,
                               chunk=cfg.loss_chunk) / groups

    def step(p, _):
        # the bf16 copy's gradient is the f32 weights' gradient (a cast's
        # transpose); every group's is taken at the step's weights
        pc = jax.tree.map(lambda a: a.astype(COMPUTE_DTYPE), p)

        def descend(q, xs):
            g = jax.grad(group_loss)(pc, *xs)
            return jax.tree.map(lambda a, b: a - lr * b.astype(a.dtype),
                                q, g), None
        p, _ = jax.lax.scan(descend, p, (tok, lab))
        return p, None

    params, _ = jax.lax.scan(step, params, None, length=epochs)
    return params


@functools.partial(jax.jit, static_argnames=("cfg",))
def lm_hits(params, tokens, labels, cfg):
    """Next-token top-1 hits over (n, S) ids, at bf16 compute."""
    pc = jax.tree.map(lambda a: a.astype(COMPUTE_DTYPE), params)
    h, _, _ = forward(pc, cfg, tokens=tokens)
    logits = logits_from_hidden(pc, cfg, h)
    return jnp.sum(jnp.argmax(logits, axis=-1) == labels)
