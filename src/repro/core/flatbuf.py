"""Flat-buffer aggregation: the federation's one merge path.

The server's merge hot loop used to walk the model pytree once per worker
per leaf (W reads + W-1 adds per leaf, re-dispatched eagerly every round).
This module flattens the model *once* into a single contiguous f32 buffer
and keeps every per-round structure persistent:

  * ``ParamBundle`` — caches treedef / shapes / dtypes / offsets for one
    model structure; ``pack``/``unpack``/``pack_many`` are jitted and the
    shapes are static, so repeated rounds hit the jit cache.
  * ``FlatServerState`` — owns a persistent ``(W_cap, N)`` stacked-update
    buffer (worker responses land in pre-allocated rows) plus the packed
    server buffer, and merges with ONE fused op:
    ``new = (1-alpha) * server + alpha * sum_i w_i * x_i``.
  * The fused op is the Pallas kernel ``kernels.fedavg_agg.fedavg_mix_flat``
    on TPU backends (single VMEM pass, server buffer donated); elsewhere the
    same math runs as one jitted XLA contraction over the packed buffer —
    identical numerics, still a single fused pass (interpret-mode Pallas
    would serialise per block on CPU; parity tests cover the kernel there).

Buffers are padded to a multiple of ``BLOCK`` lanes so the kernel grid
divides evenly and the padded tail (zeros in both server and updates)
stays zero through every merge.

Sharded substrate: pass ``mesh=`` (a 1-D ``parallel.sharding.agg_mesh``)
and the whole flat layer shards along the packed parameter axis N —
``ParamBundle`` pads N to ``BLOCK * n_shards`` divisibility and carries a
``NamedSharding`` (vectors ``P('agg')``, the (W, N) row buffer
``P(None, 'agg')``), pack jits pin their outputs to it, unpack returns
trees replicated over the mesh (what training and evaluation consume),
and the fused merge dispatches per shard (``shard_map``-ed Pallas kernel
on TPU, a GSPMD-partitioned XLA contraction elsewhere).  The packed layout keeps
every worker's lane of a parameter on one device, so the W-reduce is
shard-local, the merge needs no collective at all, and no host ever
materialises the full (W, N) buffer — per-device live bytes shrink
linearly with mesh size.  A 1-device mesh is bit-identical to the
unsharded path (pinned by tests/test_golden_histories.py +
tests/test_agg_sharded.py).
"""
from __future__ import annotations

import functools
import heapq
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import fedavg_agg, pallas_flags
from repro.parallel import sharding as psharding
from repro.runtime import trace

BLOCK = 512          # kernel tile width; pack pads N up to a multiple


def padded_size_for(n_params: int, n_shards: int = 1) -> int:
    """Packed width of an ``n_params`` model on an ``n_shards`` server
    mesh: a multiple of ``BLOCK * n_shards`` so the buffer splits evenly
    and every device's slice stays BLOCK-aligned for the kernel grid."""
    lane = BLOCK * max(1, int(n_shards))
    return -(-int(n_params) // lane) * lane


def shard_spans(lo: int, hi: int, shard_size: int) -> Tuple[tuple, ...]:
    """Mesh-aware offsets: split the global param range ``[lo, hi)`` into
    shard-local slices, one ``(shard, local_lo, local_hi, global_lo)``
    tuple per device the range touches (a leaf crossing a shard boundary
    owns one span per device)."""
    spans = []
    d = lo // shard_size
    while lo < hi:
        end = min(hi, (d + 1) * shard_size)
        spans.append((d, lo - d * shard_size, end - d * shard_size, lo))
        lo, d = end, d + 1
    return tuple(spans)


class ParamBundle:
    """Pack/unpack one model structure to/from a flat f32 buffer.

    Offsets, shapes and dtypes are computed once at construction; the jitted
    pack/unpack close over them as static data, so every later call with the
    same structure is a cache hit.

    With ``mesh`` (1-D server mesh over the ``agg`` axis): N pads up to
    ``BLOCK * n_shards`` divisibility, the bundle carries the vector/row
    ``NamedSharding``s, and every pack jit pins its output to them — the
    runtime path works on whole logically-global arrays and lets
    jax place the shards.  :meth:`shard_bounds`/:meth:`leaf_spans` expose
    the resulting mesh-aware offset table (which device owns which slice
    of which leaf) for introspection: the parity/property tiers assert
    the layout against it, and partial-shard consumers (per-shard
    checkpointing, debugging) read it rather than re-deriving padding.
    """

    def __init__(self, template, mesh=None):
        leaves, treedef = jax.tree.flatten(template)
        if not leaves:
            raise ValueError("cannot bundle an empty pytree")
        self.treedef = treedef
        self.shapes: Tuple[tuple, ...] = tuple(tuple(l.shape) for l in leaves)
        self.dtypes = tuple(jnp.asarray(l).dtype for l in leaves)
        self.sizes = tuple(int(np.prod(s, dtype=np.int64)) if s else 1
                           for s in self.shapes)
        off = np.concatenate([[0], np.cumsum(self.sizes)])
        self.offsets = tuple(int(o) for o in off[:-1])
        self.n_params = int(off[-1])
        # bytes of the model at its native dtypes — what a raw (uncoded)
        # wire transfer of this structure costs (core/transport.py)
        self.raw_bytes = int(sum(n * jnp.dtype(d).itemsize
                                 for n, d in zip(self.sizes, self.dtypes)))
        self.mesh = mesh
        self.n_shards = (1 if mesh is None
                         else int(mesh.shape[psharding.AGG_AXIS]))
        self.padded_size = padded_size_for(self.n_params, self.n_shards)
        self.shard_size = self.padded_size // self.n_shards
        if mesh is None:
            self.vec_sharding = self.row_sharding = None
            vkw = rkw = tkw = {}
        else:
            self.vec_sharding = psharding.agg_vec_sharding(mesh)
            self.row_sharding = psharding.agg_row_sharding(mesh)
            vkw = {"out_shardings": self.vec_sharding}
            rkw = {"out_shardings": self.row_sharding}
            tkw = {"out_shardings": psharding.agg_tree_sharding(mesh)}
        self._pack = jax.jit(self._pack_impl, **vkw)
        self._unpack = jax.jit(self._unpack_impl, **tkw)
        self._pack_many = jax.jit(self._pack_many_impl, **rkw)
        # stale rows beyond the live W are zeroed, not just weight-0-masked:
        # a non-finite value left by a past round would turn 0 * inf into
        # NaN inside the fused contraction
        self._pack_rows = jax.jit(
            lambda rows, trees: rows.at[:len(trees)].set(
                self._pack_many_impl(trees)).at[len(trees):].set(0.0),
            donate_argnums=(0,), **rkw)
        # same row-landing for already-packed vectors (the transport layer
        # decodes payloads straight to flat vectors — no pytree intermediate)
        self._set_rows = jax.jit(
            lambda rows, vecs: rows.at[:len(vecs)].set(
                jnp.stack(vecs)).at[len(vecs):].set(0.0),
            donate_argnums=(0,), **rkw)

    # --- mesh-aware offsets ---
    def shard_bounds(self, shard: int) -> Tuple[int, int]:
        """Global ``[lo, hi)`` param range device ``shard`` owns."""
        if not 0 <= shard < self.n_shards:
            raise IndexError(shard)
        return shard * self.shard_size, (shard + 1) * self.shard_size

    def leaf_spans(self, leaf: int) -> Tuple[tuple, ...]:
        """Shard-local slices of leaf ``leaf``: ``(shard, local_lo,
        local_hi, global_lo)`` per device the leaf touches."""
        o = self.offsets[leaf]
        return shard_spans(o, o + self.sizes[leaf], self.shard_size)

    # --- impls (jitted once per bundle) ---
    def _pack_impl(self, tree):
        parts = [jnp.asarray(l).reshape(-1).astype(jnp.float32)
                 for l in jax.tree.leaves(tree)]
        pad = self.padded_size - self.n_params
        if pad:
            parts.append(jnp.zeros((pad,), jnp.float32))
        return jnp.concatenate(parts)

    def _pack_many_impl(self, trees: tuple):
        return jnp.stack([self._pack_impl(t) for t in trees])

    def _unpack_impl(self, flat):
        leaves = [flat[o:o + n].reshape(s).astype(d)
                  for o, n, s, d in zip(self.offsets, self.sizes,
                                        self.shapes, self.dtypes)]
        return jax.tree.unflatten(self.treedef, leaves)

    # --- public API ---
    def pack(self, tree) -> jnp.ndarray:
        """tree -> (padded_size,) f32 flat buffer (zero tail)."""
        return self._pack(tree)

    def pack_many(self, trees: Sequence) -> jnp.ndarray:
        """[tree] * W -> (W, padded_size) stacked flat buffers."""
        return self._pack_many(tuple(trees))

    def pack_into(self, rows: jnp.ndarray, trees: Sequence) -> jnp.ndarray:
        """Pack W trees into the first W rows of the persistent buffer in
        ONE jitted dispatch. ``rows`` is donated (updated in place)."""
        return self._pack_rows(rows, tuple(trees))

    def unpack(self, flat: jnp.ndarray):
        """(padded_size,) or (n_params,) buffer -> tree (original dtypes)."""
        return self._unpack(flat)


_BUNDLES: Dict[tuple, ParamBundle] = {}


def bundle_for(template, mesh=None) -> ParamBundle:
    """Memoised ParamBundle keyed on (structure, shapes, dtypes, mesh) —
    the server and its transport resolve to the SAME sharded bundle, so
    decoded payload vectors land in the row buffer shape-exactly.

    The one packability rule of the federation: a weight tree whose leaves
    are not all arrays is rejected here, naming the first such leaf."""
    leaves, treedef = jax.tree.flatten(template)
    for i, l in enumerate(leaves):
        if not (hasattr(l, "shape") and hasattr(l, "dtype")):
            path, _ = jax.tree_util.tree_leaves_with_path(template)[i]
            raise ValueError(
                f"weight leaf {jax.tree_util.keystr(path)} is a "
                f"{type(l).__name__}, not an array: the model does not pack "
                "into one flat buffer")
    key = (treedef, tuple((tuple(l.shape), str(jnp.asarray(l).dtype))
                          for l in leaves), mesh)
    b = _BUNDLES.get(key)
    if b is None:
        b = _BUNDLES[key] = ParamBundle(template, mesh=mesh)
    return b


# --- fused merge ops -------------------------------------------------------
# wvec = [server_scale, w_0 .. w_{Wcap-1}]; rows beyond the live W carry
# weight 0, so capacity growth never changes the result — only the jit key.
# Every contraction runs at full f32 precision, as the Pallas kernel does.
HIGHEST = fedavg_agg.HIGHEST


def _fused_mix(server_flat, rows, wvec, use_pallas: bool, interpret: bool):
    if use_pallas:
        return fedavg_agg.fedavg_mix_flat(rows, wvec[1:], server_flat,
                                          wvec[0], block_n=BLOCK,
                                          interpret=interpret)
    return wvec[0] * server_flat + jax.lax.dot_general(
        wvec[1:], rows, (((0,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)


_fused_mix_jit = jax.jit(_fused_mix, donate_argnums=(0,),
                         static_argnames=("use_pallas", "interpret"))


def _weighted_sum(rows, w, use_pallas: bool, interpret: bool):
    if use_pallas:
        return fedavg_agg.fedavg_agg_flat(rows, w, block_n=BLOCK,
                                          interpret=interpret)
    return jax.lax.dot_general(w, rows, (((0,), (0,)), ((), ())),
                               precision=HIGHEST,
                               preferred_element_type=jnp.float32)


_weighted_sum_jit = jax.jit(_weighted_sum,
                            static_argnames=("use_pallas", "interpret"))


# sharded dispatch: per-(mesh, flags) jits, cached so repeated rounds hit
# the jit cache exactly like the unsharded path.  The XLA branch is the
# SAME contraction as `_fused_mix` (GSPMD keeps it shard-local along N, no
# collective — asserted in tests), so a 1-device mesh is bit-identical to
# the unsharded jit; the Pallas branch shard_maps the fused kernel.

@functools.lru_cache(maxsize=None)
def _sharded_mix_jit(mesh, use_pallas: bool, interpret: bool):
    vs = psharding.agg_vec_sharding(mesh)
    rs = psharding.agg_row_sharding(mesh)

    def mix(server_flat, rows, wvec):
        if use_pallas:
            return fedavg_agg.fedavg_mix_flat_sharded(
                rows, wvec[1:], server_flat, wvec[0], mesh=mesh,
                axis=psharding.AGG_AXIS, block_n=BLOCK, interpret=interpret)
        rows = jax.lax.with_sharding_constraint(rows, rs)
        server_flat = jax.lax.with_sharding_constraint(server_flat, vs)
        return wvec[0] * server_flat + jax.lax.dot_general(
            wvec[1:], rows, (((0,), (0,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32)

    return jax.jit(mix, donate_argnums=(0,), out_shardings=vs)


@functools.lru_cache(maxsize=None)
def _sharded_wsum_jit(mesh, use_pallas: bool, interpret: bool):
    vs = psharding.agg_vec_sharding(mesh)
    rs = psharding.agg_row_sharding(mesh)

    def wsum(rows, w):
        if use_pallas:
            return fedavg_agg.fedavg_agg_flat_sharded(
                rows, w, mesh=mesh, axis=psharding.AGG_AXIS, block_n=BLOCK,
                interpret=interpret)
        rows = jax.lax.with_sharding_constraint(rows, rs)
        return jax.lax.dot_general(w, rows, (((0,), (0,)), ((), ())),
                                   precision=HIGHEST,
                                   preferred_element_type=jnp.float32)

    return jax.jit(wsum, out_shardings=vs)


def fused_merge(server_flat, rows, wvec, use_pallas: Optional[bool] = None,
                interpret: Optional[bool] = None, mesh=None):
    """One-pass ``wvec[0]*server + wvec[1:] @ rows`` on packed buffers.

    ``server_flat`` is donated — callers must treat it as consumed.  With
    ``mesh`` the buffers are N-sharded and the pass runs per shard.
    """
    use_pallas, interpret = pallas_flags(use_pallas, interpret)
    wv = trace.to_device(wvec, jnp.float32)
    if mesh is not None:
        return _sharded_mix_jit(mesh, use_pallas, interpret)(
            server_flat, rows, wv)
    return _fused_mix_jit(server_flat, rows, wv,
                          use_pallas=use_pallas, interpret=interpret)


def fused_weighted_sum(rows, w, use_pallas: Optional[bool] = None,
                       interpret: Optional[bool] = None, mesh=None):
    """One-pass ``w @ rows`` (no server term — the alpha>=1 replace-on-
    aggregate case must not read the server buffer at all: the oracle
    ``ref.reference_mix_into`` short-circuits there, and ``0 * server`` would turn a
    non-finite server model into NaN instead of replacing it)."""
    use_pallas, interpret = pallas_flags(use_pallas, interpret)
    wv = trace.to_device(w, jnp.float32)
    if mesh is not None:
        return _sharded_wsum_jit(mesh, use_pallas, interpret)(rows, wv)
    return _weighted_sum_jit(rows, wv,
                             use_pallas=use_pallas, interpret=interpret)


def normalized_weights(weights: Sequence[float]) -> np.ndarray:
    w = np.asarray(weights, dtype=np.float64)
    s = w.sum()
    if s <= 0:
        raise ValueError("aggregation weights sum to zero")
    return (w / s).astype(np.float32)


class FlatServerState:
    """Persistent flat-buffer merge state for one AggregationServer.

    Keeps (a) the packed server model, mirrored against the pytree the
    server hands us (re-packed only if the server's tree is not the one we
    produced), and (b) a pre-allocated (W_cap, N) row buffer that worker
    updates are packed into — no fresh ``jnp.stack`` per leaf per round.

    With ``mesh`` both live buffers shard along N over the 1-D server
    mesh (rows ``P(None, 'agg')``, server mirror ``P('agg')``) and every
    merge runs per shard — per-device peak live bytes of the substrate
    shrink linearly with mesh size.
    """

    def __init__(self, template, use_pallas: Optional[bool] = None,
                 mesh=None):
        self.bundle = bundle_for(template, mesh)
        self.use_pallas = use_pallas
        self.mesh = mesh
        # optional core.server_opt.ServerOpt: transforms the packed merge
        # result in _finish (one fused elementwise pass) before unpack
        self.server_opt = None
        self._rows: Optional[jnp.ndarray] = None
        # --- cohort row window (win_claim/win_write/win_release) ---
        # recycled rows, a min-heap: claims reuse the LOWEST free index,
        # so a sync round's arrivals land in rows [0..n) in arrival order
        # — the exact layout merge_rows produces, which is what makes the
        # windowed merge bit-identical at cohort=W
        self._free: list = []
        self._next_row = 0            # high-water mark of ever-claimed rows
        # rows of the window's first allocation: the server sets its cohort,
        # so a round's rows fit without the 2x growth (a row of a large
        # model is a whole model's bytes)
        self.window_rows = 8
        # released-but-not-yet-zeroed rows: zeroing is deferred and batched
        # into one scatter right before the next merge (a stale non-finite
        # value would turn 0 * inf into NaN inside the fused contraction)
        self._dirty: set = set()
        rkw = ({} if mesh is None
               else {"out_shardings": self.bundle.row_sharding})
        self._win_set = jax.jit(
            lambda rows, vec, row: rows.at[row].set(vec),
            donate_argnums=(0,), **rkw)
        self._win_zero = jax.jit(
            lambda rows, idx: rows.at[idx].set(0.0),
            donate_argnums=(0,), **rkw)

    @property
    def capacity(self) -> int:
        return 0 if self._rows is None else int(self._rows.shape[0])

    def row_bytes_by_device(self) -> Dict[str, int]:
        """Bytes of the (W, N) row buffer held on each device."""
        if self._rows is None:
            return {}
        return {str(s.device): int(s.data.nbytes)
                for s in self._rows.addressable_shards}

    def _ensure_capacity(self, w: int):
        if self.capacity >= w:
            return
        shape = (w, self.bundle.padded_size)
        if self.mesh is None:
            new = jnp.zeros(shape, jnp.float32)
            if self._rows is not None:
                new = new.at[:self.capacity].set(self._rows)
        elif self._rows is None:
            # allocate sharded from the start — a replicated-then-reshard
            # zeros would spike the full (W, N) buffer onto one device,
            # exactly what the mesh exists to avoid
            new = jnp.zeros(shape, jnp.float32,
                            device=self.bundle.row_sharding)
        else:
            # rare growth path (W grew): jitted so the copy never leaves
            # the shards (re-traced per capacity, which only ever grows)
            new = jax.jit(
                lambda r: jnp.zeros(shape, jnp.float32).at[:r.shape[0]]
                .set(r), out_shardings=self.bundle.row_sharding)(self._rows)
        self._rows = new

    def _server_buffer(self, server_tree) -> jnp.ndarray:
        """The server model packed, for the merges that read it (alpha < 1,
        deltas).  Packed when needed and donated to the merge, not kept
        beside the tree: at 10^8 parameters a standing packed copy of the
        model is gigabytes of device memory."""
        return self.bundle.pack(server_tree)

    def merge(self, server_tree, update_trees: Sequence,
              weights: Sequence[float], alpha: float = 1.0):
        """Fused ``(1-alpha)*server + alpha * sum_i w_hat_i * x_i``.

        Returns the merged pytree (original dtypes).
        """
        n = len(update_trees)
        self._ensure_capacity(n)
        self._rows = self.bundle.pack_into(self._rows, update_trees)
        return self._merge_rows_tail(server_tree, n, weights, alpha)

    def merge_rows(self, server_tree, update_vecs: Sequence,
                   weights: Sequence[float], alpha: float = 1.0):
        """Same fused merge, but the updates are already-packed flat vectors
        (``(padded_size,)`` f32) — the transport layer's decode path lands
        straight in the persistent row buffer with no pytree intermediate."""
        n = len(update_vecs)
        self._ensure_capacity(n)
        self._rows = self.bundle._set_rows(self._rows, tuple(update_vecs))
        return self._merge_rows_tail(server_tree, n, weights, alpha)

    def _merge_rows_tail(self, server_tree, n: int,
                         weights: Sequence[float], alpha: float):
        w = normalized_weights(weights)
        if alpha >= 1.0:
            # replace-on-aggregate: no server term (matches the oracle's
            # short-circuit; also skips the server read entirely)
            wv = np.zeros((self.capacity,), np.float32)
            wv[:n] = w
            merged = fused_weighted_sum(self._rows, wv, self.use_pallas,
                                        mesh=self.mesh)
        else:
            wvec = np.zeros((self.capacity + 1,), np.float32)
            wvec[0] = 1.0 - alpha
            wvec[1:1 + n] = alpha * w
            server_flat = self._server_buffer(server_tree)
            merged = fused_merge(server_flat, self._rows, wvec,
                                 self.use_pallas, mesh=self.mesh)
        return self._finish(server_tree, merged)

    def _finish(self, server_tree, merged):
        """Shared merge epilogue: optional server-optimizer pass (in
        packed space — the whole point of the flat substrate), unpack.
        With ``server_opt=None`` this is byte-for-byte the old tail
        (golden-pinned)."""
        if self.server_opt is not None:
            merged = self.server_opt.step_vec(self, server_tree, merged)
        out = self.bundle.unpack(merged)
        if self.server_opt is not None:
            self.server_opt.note_result(merged, out)
        return out

    # --- cohort row window --------------------------------------------
    # At massive scale the (W, N) row buffer is the memory wall: a
    # 10k-worker population must NOT allocate 10k rows when only a
    # 64-worker cohort is ever in flight.  The window keeps the SAME
    # persistent buffer but sizes it by concurrent in-flight updates:
    # each arriving update claims a row (lowest free index first),
    # streams its vector in, and the merge contracts the window with the
    # per-update weight scattered to its claimed row — same fused kernel,
    # lane -> worker indirection in the weight vector.  Rows recycle on
    # release, so peak memory is O(max concurrent updates x N), and at
    # cohort=W the claim order degenerates to merge_rows' [0..n) layout,
    # keeping the result bit-identical (pinned in tests/test_scale.py).

    def win_claim(self) -> int:
        """Claim a free row of the window for one in-flight update."""
        if self._free:
            return heapq.heappop(self._free)
        row = self._next_row
        self._next_row += 1
        if row >= self.capacity:
            # geometric growth: per-claim exact growth would copy the
            # whole buffer O(window) times (extra capacity is harmless —
            # zero rows at zero weight never change the merge result)
            self._ensure_capacity(max(row + 1, 2 * self.capacity,
                                      self.window_rows))
        return row

    def win_write(self, row: int, vec) -> None:
        """Land one already-packed update vector in its claimed row."""
        idx = np.int32(row)
        self._rows = self._win_set(self._rows, vec, idx)
        trace.count("h2d_bytes", idx.nbytes)
        self._dirty.discard(row)

    def win_release(self, row: int) -> None:
        """Recycle a row: its update was merged (or abandoned).  The stale
        data is zeroed lazily — batched into the next merge."""
        heapq.heappush(self._free, row)
        self._dirty.add(row)

    def _flush_dirty(self) -> None:
        if not self._dirty:
            return
        idx = np.fromiter(self._dirty, np.int32, len(self._dirty))
        self._rows = self._win_zero(self._rows, idx)
        trace.count("h2d_bytes", idx.nbytes)
        self._dirty.clear()

    def merge_window(self, server_tree, rows: Sequence[int],
                     weights: Sequence[float], alpha: float = 1.0):
        """Fused merge over the row window: ``rows[i]`` (a claimed row
        index) carries the update weighted by ``weights[i]``; every other
        row of the window contributes weight 0.  Same contraction as
        :meth:`merge_rows`, same return convention."""
        self._flush_dirty()
        w = normalized_weights(weights)
        idx = np.asarray(tuple(rows), np.intp)
        if alpha >= 1.0:
            wv = np.zeros((self.capacity,), np.float32)
            wv[idx] = w
            merged = fused_weighted_sum(self._rows, wv, self.use_pallas,
                                        mesh=self.mesh)
        else:
            wvec = np.zeros((self.capacity + 1,), np.float32)
            wvec[0] = 1.0 - alpha
            wvec[idx + 1] = alpha * w
            server_flat = self._server_buffer(server_tree)
            merged = fused_merge(server_flat, self._rows, wvec,
                                 self.use_pallas, mesh=self.mesh)
        return self._finish(server_tree, merged)

    def row_vec(self, row: int) -> jnp.ndarray:
        """Read one claimed row back as a packed flat vector (the
        async_delta path applies per-update deltas straight off the
        window)."""
        return self._rows[row]

    def apply_delta(self, cur_tree, new_tree, base_tree):
        """``cur + (new - base)`` as one fused pass over packed buffers
        (async_delta response handling — the delta-accumulate variant with
        a single signed-weight delta)."""
        rows = self.bundle.pack_many((new_tree, base_tree))
        cur = self.bundle.pack(cur_tree)
        out = fused_merge(cur, rows, np.asarray([1.0, 1.0, -1.0], np.float32),
                          self.use_pallas, mesh=self.mesh)
        return self.bundle.unpack(out)

    def delta_vec(self, cur_tree, new_vec, base_vec) -> jnp.ndarray:
        """``cur + (new - base)`` where new/base are already-packed flat
        vectors; returns the packed result (async_delta keeps everything
        in flat-vector space).

        Reuses the packed server mirror when ``cur_tree`` is the tree the
        last merge produced — no fresh O(N) pack per response. The mirror
        is consumed (donated into the fused op); a following alpha<1 merge
        re-packs, but the default async_delta aggregate (alpha>=1) never
        reads the server buffer at all."""
        rows = jnp.stack([new_vec, base_vec])
        cur = self._server_buffer(cur_tree)
        return fused_merge(cur, rows,
                           np.asarray([1.0, 1.0, -1.0], np.float32),
                           self.use_pallas, mesh=self.mesh)
