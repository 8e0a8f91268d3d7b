"""Pod-level federated training: the paper's FL mechanism as a first-class
multi-pod distributed-training feature.

Mapping (DESIGN.md §2): each pod is an FL *worker*; the aggregation server is
the cross-pod reduction. Params carry a leading ``n_pods`` dim sharded over
the ``pod`` mesh axis, and the per-step ``fl_local_step`` is a ``jax.vmap``
of the ordinary sharded ``train_step`` over that dim — so gradients reduce
over (``data``, ``model``) only and *no pod-axis collective exists in the
per-step HLO*. ``fl_round`` is the aggregation server: a staleness/selection-
weighted average over the pod dim (one parameter-sized pod all-reduce every H
steps — the paper's "j local epochs before responding").

This is exactly the thesis' FedAvg/local-SGD with worker selection, where the
scarce cross-pod link plays the role of the edge worker's WAN uplink.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import train_step


def stack_for_pods(tree, n_pods: int):
    """Replicate a pytree with a new leading pod dim (worker-local copies)."""
    return jax.tree.map(
        lambda p: jnp.broadcast_to(p[None], (n_pods,) + p.shape), tree)


def unstack_pod(tree, idx: int = 0):
    return jax.tree.map(lambda p: p[idx], tree)


def fl_local_step(stacked_params, stacked_opt, batch, *, cfg, optimizer,
                  n_pods: int, n_microbatch: int = 1):
    """One local-SGD step on every pod worker independently.

    batch leaves are (B_global, ...) and get reshaped to (n_pods, B/n_pods,
    ...) so the pod dim lines up with the stacked params.
    """
    def split(x):
        return x.reshape((n_pods, x.shape[0] // n_pods) + x.shape[1:])
    pb = jax.tree.map(split, batch)
    step = functools.partial(train_step, cfg=cfg, optimizer=optimizer,
                             n_microbatch=n_microbatch)
    from repro.parallel.sharding import pod_axis_is_vmapped
    with pod_axis_is_vmapped():
        return jax.vmap(step)(stacked_params, stacked_opt, pb)


def _use_agg_kernel() -> bool:
    # the Pallas kernel is single-device; on the multi-pod production mesh
    # (and on CPU, where interpret mode would serialise per block) the same
    # math runs as one fused XLA contraction over the packed buffer
    return jax.default_backend() == "tpu" and jax.device_count() == 1


def _use_flat_round() -> bool:
    # packing materialises an (n_pods, N) f32 copy of the whole model; on a
    # single device that buys the one-pass fused merge, but on the sharded
    # production mesh it would add ~n_pods x model-size f32 of peak HBM on
    # top of the (donated) stacked params — there the per-leaf einsum keeps
    # only per-leaf temporaries
    return jax.device_count() == 1


def _pack_pods(stacked_params):
    """Flatten every (n_pods, ...) leaf once into a single contiguous
    (n_pods, N) f32 buffer; returns (flat, leaves, treedef) with static
    shapes so repeated rounds hit the jit cache."""
    leaves, treedef = jax.tree.flatten(stacked_params)
    n_pods = leaves[0].shape[0]
    flat = jnp.concatenate(
        [l.reshape(n_pods, -1).astype(jnp.float32) for l in leaves], axis=1)
    return flat, leaves, treedef


def _unpack_pods(merged, leaves, treedef):
    out, off = [], 0
    for l in leaves:
        size = l[0].size
        lm = merged[off:off + size].reshape(l.shape[1:])
        out.append(jnp.broadcast_to(lm[None], l.shape).astype(l.dtype))
        off += size
    return jax.tree.unflatten(treedef, out)


def fl_round(stacked_params, weights):
    """Aggregation server: weighted average over the pod dim, re-broadcast.

    ``weights``: (n_pods,) — selection mask x aggregation weight (FedAvg:
    1/|selected|; staleness-weighted: eqs 2.3-2.7 computed host-side by the
    ``AggregationServer``). Non-selected workers keep training on the merged
    model (their next round starts from the aggregate, as in the thesis'
    synchronous mode); weight 0 removes their contribution.

    Routes through the flat-buffer fast path on a single device: the whole
    pytree is packed into one (n_pods, N) buffer and merged in a single
    pass (the fused Pallas kernel on TPU, one XLA contraction on CPU). On
    a multi-device mesh the per-leaf einsum is kept — see _use_flat_round.
    """
    n_pods = weights.shape[0]
    w = weights / jnp.maximum(weights.sum(), 1e-9)
    if not _use_flat_round():
        def agg(p):
            merged = jnp.einsum("p...,p->...", p.astype(jnp.float32), w)
            return jnp.broadcast_to(merged[None], (n_pods,) + merged.shape
                                    ).astype(p.dtype)
        return jax.tree.map(agg, stacked_params)
    flat, leaves, treedef = _pack_pods(stacked_params)
    if _use_agg_kernel():
        from repro.kernels import fedavg_agg
        merged = fedavg_agg.fedavg_agg_flat(flat, w)
    else:
        merged = jnp.einsum("pn,p->n", flat, w)
    return _unpack_pods(merged, leaves, treedef)


def fl_round_delta_compressed(stacked_params, anchor_params, weights, *,
                              compressor):
    """Beyond-paper variant: aggregate *compressed deltas* from the anchor
    (last merged model) instead of raw weights — the top-k/int8 codecs of
    core/transport.py (per-tensor oracles in kernels/ref.py).

    Deltas are compressed on the packed (n_pods, N) buffer, so top-k style
    compressors rank the whole model's coordinates globally (FedLab-style
    composable pipeline) rather than per leaf.
    """
    n_pods = weights.shape[0]
    w = weights / jnp.maximum(weights.sum(), 1e-9)
    flat, leaves, treedef = _pack_pods(stacked_params)
    aflat = jnp.concatenate(
        [l.reshape(-1).astype(jnp.float32)
         for l in jax.tree.leaves(anchor_params)])
    cdelta = compressor(flat - aflat[None])
    if _use_agg_kernel():
        from repro.kernels import fedavg_agg
        merged = fedavg_agg.fedavg_delta_flat(aflat, cdelta, w)
    else:
        merged = aflat + jnp.einsum("pn,p->n", cdelta, w)
    return _unpack_pods(merged, leaves, treedef)
