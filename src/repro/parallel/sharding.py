"""Sharding recipes: parameter, batch and decode-state PartitionSpecs.

Layout (production mesh, v5e):
  * ``data``  — FSDP/ZeRO: weights + optimizer state sharded along a weight
                dim; gathered per-layer inside the rematted scan. Batch is
                data-parallel over (``pod``, ``data``).
  * ``model`` — tensor parallel: attention heads / FFN hidden / vocab /
                experts (phi3.5) / mamba2 inner channels.
  * ``pod``   — data-parallel across pods in the sync baseline; the
                *federated* axis for the paper's technique (local SGD per pod,
                cross-pod weight aggregation every H steps).
  * ``agg``   — the aggregation-*server* mesh (core/flatbuf.py): the packed
                flat parameter axis N of the server model and the (W, N)
                update-row buffer shard 1-D over it, so per-device live bytes
                of the merge substrate shrink linearly with mesh size.

A dim is only sharded when divisible by the axis size, so the same rules
serve the 256-chip pod, the 512-chip 2-pod mesh, and single-device tests.
Known replication fallbacks (documented in EXPERIMENTS.md): rwkv6 heads (40)
and gemma2/musicgen head counts don't divide 16 -> their attention/time-mix
projections stay FSDP-only.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _sizes(mesh):
    return dict(zip(mesh.axis_names, mesh.devices.shape))


# ---------------------------------------------------------------------------
# Aggregation-server mesh (the sharded flat-buffer merge substrate)
# ---------------------------------------------------------------------------

AGG_AXIS = "agg"


def agg_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D aggregation-server mesh over ``AGG_AXIS`` (the first
    ``n_devices`` local devices; all of them when None)."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else int(n_devices)
    if not 1 <= n <= len(devs):
        raise ValueError(f"server mesh of {n} devices, but only "
                         f"{len(devs)} available (CPU runs: set "
                         f"XLA_FLAGS=--xla_force_host_platform_device_count)")
    return Mesh(np.asarray(devs[:n]), (AGG_AXIS,))


def agg_vec_spec() -> P:
    """Packed flat parameter vector (N,): sharded along N."""
    return P(AGG_AXIS)


def agg_row_spec() -> P:
    """(W, N) update-row buffer: worker rows replicated, N sharded — every
    device holds ALL workers' slices of its own parameter range, so the
    W-reduce of the merge is shard-local (no collective)."""
    return P(None, AGG_AXIS)


def agg_vec_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, agg_vec_spec())


def agg_row_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, agg_row_spec())


def agg_tree_sharding(mesh: Mesh) -> NamedSharding:
    """Unpacked model tree: every leaf replicated over the server mesh.
    Its consumers (a worker's local training, evaluation) then run the
    same unpartitioned program as on one device; leaves split along the
    packed axis would partition their contractions across the mesh and
    change the reduction order."""
    return NamedSharding(mesh, P())


def dp_axes(mesh) -> Tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _dp_total(mesh) -> int:
    s = _sizes(mesh)
    out = 1
    for a in dp_axes(mesh):
        out *= s[a]
    return out


def named(mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


# ---------------------------------------------------------------------------
# Trace-time activation constraints (sequence parallelism)
# ---------------------------------------------------------------------------

import contextlib as _contextlib
import threading as _threading

_TLS = _threading.local()


@_contextlib.contextmanager
def pod_axis_is_vmapped():
    """Inside ``fl_local_step`` the pod axis is the vmapped (stacked) dim —
    activation constraints must NOT claim it for the within-pod batch."""
    prev = getattr(_TLS, "no_pod", False)
    _TLS.no_pod = True
    try:
        yield
    finally:
        _TLS.no_pod = prev


def current_mesh_axes():
    """Axis-name -> size of the mesh active at trace time ({} outside jit /
    without a mesh context). Hides the pod axis under fl vmap."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return {}
    axes = dict(am.shape)
    if getattr(_TLS, "no_pod", False):
        axes.pop("pod", None)
    return axes


def constrain_qkv(q, k, v):
    """Attention-input layout: q head-sharded over ``model`` when the head
    count divides (TP attention: K/V gathered once per layer, scores local
    per head shard); otherwise q stays *sequence*-sharded (attention compute
    splits over query rows) with K/V replicated over ``model``. Either way
    K/V stop being seq-sharded — without this GSPMD re-gathers K/V once per
    KV-block inside the scan."""
    axes = current_mesh_axes()
    if not axes or "model" not in axes:
        return q, k, v
    m = axes["model"]
    dp = tuple(a for a in ("pod", "data") if a in axes)
    dp_n = 1
    for a in dp:
        dp_n *= axes[a]
    B, S, H, _ = q.shape
    Kv = k.shape[2]
    b_ax = dp if (dp and B % dp_n == 0) else None
    if H % m == 0:
        q_spec = P(b_ax, None, "model", None)
    elif S % m == 0 and S > 1:
        q_spec = P(b_ax, "model", None, None)
    else:
        q_spec = P(b_ax, None, None, None)
    kv_head_ax = "model" if (Kv % m == 0 and H % m == 0) else None
    kv_spec = P(b_ax, None, kv_head_ax, None)
    q = jax.lax.with_sharding_constraint(q, q_spec)
    k = jax.lax.with_sharding_constraint(k, kv_spec)
    v = jax.lax.with_sharding_constraint(v, kv_spec)
    return q, k, v


def constrain_act(x):
    """Residual-stream constraint: batch over (pod,)data, seq over model
    (Megatron-style sequence parallelism). No-op when no mesh is active or
    dims don't divide; this keeps the rematted scan carry fully sharded."""
    axes = current_mesh_axes()
    if not axes or x.ndim < 2:
        return x
    dp = tuple(a for a in ("pod", "data") if a in axes)
    dp_n = 1
    for a in dp:
        dp_n *= axes[a]
    b_ax = dp if (dp and x.shape[0] % dp_n == 0) else None
    s_ax = "model" if ("model" in axes and x.ndim >= 3 and
                       x.shape[1] % axes["model"] == 0 and x.shape[1] > 1) else None
    spec = P(b_ax, s_ax, *([None] * (x.ndim - 2)))
    return jax.lax.with_sharding_constraint(x, spec)


def to_named_tree(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def _pspec(path_names, shape, mesh) -> P:
    s = _sizes(mesh)
    m, d = s.get("model", 1), s.get("data", 1)

    def tp(i):   # shard dim i over "model" when divisible
        return "model" if shape[i] % m == 0 else None

    def fs(i):   # shard dim i over "data" (FSDP) when divisible
        return "data" if shape[i] % d == 0 else None

    name = path_names[-1]
    parent = path_names[-2] if len(path_names) > 1 else ""
    r = len(shape)

    def pad(*trailing) -> P:
        return P(*([None] * (r - len(trailing)) + list(trailing)))

    if name == "embedding":
        return pad(tp(r - 2), fs(r - 1))
    if parent == "attn":
        if name == "wq":
            return pad(fs(r - 3), tp(r - 2), None)
        if name in ("wk", "wv"):
            return pad(fs(r - 3), tp(r - 2), None)
        if name == "wo":
            return pad(tp(r - 3), None, fs(r - 1))
    if parent == "mlp":
        if name in ("wi_gate", "wi_up"):
            return pad(fs(r - 2), tp(r - 1))
        if name == "wo":
            return pad(tp(r - 2), fs(r - 1))
    if parent == "moe":
        if name == "router":
            return pad(fs(r - 2), None)
        ep = shape[r - 3] % m == 0          # experts divisible -> EP
        if name in ("wi_gate", "wi_up"):
            return pad("model", fs(r - 2), None) if ep else \
                pad(None, fs(r - 2), tp(r - 1))
        if name == "wo":
            return pad("model", None, fs(r - 1)) if ep else \
                pad(None, tp(r - 2), fs(r - 1))
    if parent == "tm":                       # rwkv6 time-mix
        if name in ("wr", "wk", "wv", "wg"):
            return pad(fs(r - 2), None)
        if name == "wo":
            return pad(None, fs(r - 1))
        if name == "decay_w1":
            return pad(fs(r - 2), None)
        if name == "decay_w2":
            return pad(None, fs(r - 1))
        if name == "mix_w1":
            return pad(fs(r - 3), None, None)
        if name == "mix_w2":
            return pad(None, None, fs(r - 1))
        return pad(*([None] * min(r, 2)))
    if parent == "cm":                       # rwkv6 channel-mix
        if name == "wk":
            return pad(fs(r - 2), tp(r - 1))
        if name == "wv":
            return pad(tp(r - 2), fs(r - 1))
        if name == "wr":
            return pad(fs(r - 2), None)
        return pad(None)
    # mamba2
    if name in ("wz", "wx"):
        return pad(fs(r - 2), tp(r - 1))
    if name in ("wB", "wC"):
        return pad(fs(r - 2), None)
    if name == "wdt":
        return pad(fs(r - 2), tp(r - 1))
    if name == "conv_x_w":
        return pad(None, tp(r - 1))
    if name in ("conv_x_b", "norm_scale"):
        return pad(tp(r - 1))
    if name in ("dt_bias", "a_log", "d_skip"):
        return pad(tp(r - 1))
    if name == "out_proj":
        return pad(tp(r - 2), fs(r - 1))
    return P(*([None] * r))


def param_specs(cfg, params_tree, mesh):
    """PartitionSpec tree matching an (eval_shape'd) params tree."""
    def f(path, leaf):
        names = [p.key for p in path if hasattr(p, "key")]
        return _pspec(names, leaf.shape, mesh)
    return jax.tree_util.tree_map_with_path(f, params_tree)


# ---------------------------------------------------------------------------
# Batch
# ---------------------------------------------------------------------------

def batch_specs(cfg, batch_tree, mesh):
    dp = dp_axes(mesh)
    total = _dp_total(mesh)

    def f(path, leaf):
        names = [p.key for p in path if hasattr(p, "key")]
        b_ok = leaf.shape[0] % total == 0
        lead = dp if b_ok else None
        rest = [None] * (len(leaf.shape) - 1)
        return P(lead, *rest)
    return jax.tree_util.tree_map_with_path(f, batch_tree)


# ---------------------------------------------------------------------------
# Decode state
# ---------------------------------------------------------------------------

def state_specs(cfg, state_tree, mesh, batch: int):
    """KV caches: batch over dp when divisible, seq over ``model``; when the
    batch can't be sharded (long_500k B=1) the cache seq axis spreads over
    every mesh axis. SSM states: batch over dp, heads/channels over model."""
    s = _sizes(mesh)
    m = s.get("model", 1)
    dp = dp_axes(mesh)
    total = _dp_total(mesh)
    b_ok = batch % total == 0
    all_axes = tuple(mesh.axis_names)

    def f(path, leaf):
        names = [p.key for p in path if hasattr(p, "key")]
        name = names[-1]
        shp = leaf.shape
        r = len(shp)

        def pad(*trailing):
            return P(*([None] * (r - len(trailing)) + list(trailing)))

        if name in ("k", "v"):               # (..., B, C, Kv, hd)
            if b_ok:
                seq_ax = "model" if shp[r - 3] % m == 0 else None
                return pad(dp, seq_ax, None, None)
            n_all = 1
            for a in all_axes:
                n_all *= s[a]
            seq_ax = all_axes if shp[r - 3] % n_all == 0 else (
                "model" if shp[r - 3] % m == 0 else None)
            return pad(None, seq_ax, None, None)
        if name == "slot_pos":               # (..., C)
            if b_ok:
                return pad("model" if shp[r - 1] % m == 0 else None)
            n_all = 1
            for a in all_axes:
                n_all *= s[a]
            return pad(all_axes if shp[r - 1] % n_all == 0 else None)
        if name == "wkv":                    # (..., B, H, K, K)
            return pad(dp if b_ok else None, None, None, None)
        if name == "shift":                  # (..., B, 1, D)
            return pad(dp if b_ok else None, None, None)
        if name == "ssm":                    # (..., B, nh, hd, n)
            nh_ax = "model" if shp[r - 3] % m == 0 else None
            return pad(dp if b_ok else None, nh_ax, None, None)
        if name in ("conv_x", "conv_bc"):    # (..., B, K-1, C)
            ch_ax = "model" if shp[r - 1] % m == 0 else None
            return pad(dp if b_ok else None, None, ch_ax)
        return P(*([None] * r))
    return jax.tree_util.tree_map_with_path(f, state_tree)
