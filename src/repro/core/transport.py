"""Wire-aware transport layer: codec'd flat-buffer weight exchange.

Every weight transfer between the aggregation server and a worker goes
through this module.  The thesis transmits full model weights over a
dedicated channel every round and its worker-selection/time model (eq 3.4)
hinges on transmission time; FLight (arXiv:2308.02834) and Das et al.
(arXiv:1911.04559) make the case that on edge links the *bytes on the wire*
dominate FL cost — so bytes are a first-class simulated quantity here, not
a side calculation.  Edge links are also asymmetric and often
downlink-constrained, so the codec registry applies in BOTH directions:
the uplink (worker -> server) response encodes a delta against the model
the worker fetched, and the downlink (server -> worker) dispatch encodes a
delta against the worker's **last-acked** state.

A :class:`Transport` owns one codec per direction and a :class:`Link` per
worker.  Codecs operate on the packed flat f32 buffer from
``flatbuf.ParamBundle`` — encode is one fused pass over a contiguous
vector (the ``kernels/topk_quant`` Pallas kernel on TPU, its XLA oracle
elsewhere), never a per-leaf tree-map — and every payload travels in a
:class:`Payload` envelope carrying its exact ``wire_bytes``.

Codec table (n = logical parameter count, k = max(1, int(n * frac)),
kept = entries actually surviving the top-k threshold):

  ============== ============================== =================== ================== ===============
  codec          uplink payload (base =         downlink payload    wire_bytes         retransmit copy
                 fetched model, ``tx_base``)    (base = last-acked                     (lossy links)
                                                state)
  ============== ============================== =================== ================== ===============
  raw            full weights at native dtypes  full weights        sum(leaf nbytes)   byte-identical
  delta          f32 delta (new - base)         f32 delta           4 * n              byte-identical
  int8           int8 delta + 1 f32 scale       same, vs acked base n + 4              byte-identical
  topk_ef        top-k delta w/ EF              same, vs acked base ceil(n/8) + 4*kept byte-identical
  topk_ef+int8   top-k + int8 on kept values    same, vs acked base ceil(n/8) + 4      byte-identical
                                                                      + kept
  auto           per-link: whichever row above  per-link, same rule the chosen row's   byte-identical
                 minimises expected latency                         cost per dispatch
  ============== ============================== =================== ================== ===============

(The bitmap term ``ceil(n/8)`` is the kept-coordinate indicator; quantised
codecs add one 4-byte per-update scale; payload values cost ``kept *
itemsize``.)  ``auto`` is not a codec but a per-dispatch *resolver*
(``core/autotune.py``): at every encode the link picks the concrete row
minimising ``expected_codec_bytes * retx_factor / measured_bandwidth +
encode_cost`` — raw on fat backbone links, sparsified on starved edge
links — pricing the advertised nominal rate until the estimator's first
measurement replaces it, with an optional forced dense warmup
(``AutoPolicy.warmup_rounds``) and a top-k frac that tightens as
accuracy plateaus (fed back per round via :meth:`Transport.note_round`).
Every payload carries the codec id it was actually encoded with, and ALL
decode/EF/ack paths resolve their spec from the payload — never from the
link's configured default — so a link can interleave raw, delta and top-k
dispatches without desynchronising; with a fixed codec configured the
payload codec always equals the configured one and every path is
bit-identical to the pre-auto behaviour (pinned by the golden histories).  All compressed codecs encode *deltas*, never raw weights, so
the reconstruction error contracts under error feedback.  Each direction
keeps its own per-link EF residual — with one crucial asymmetry.  The
uplink compresses ``delta + residual`` (the worker's base is reset by
every dispatch, so dropped mass is gone unless explicitly carried
forward).  The downlink compresses ``model - acked_base`` alone: because
``acked_base`` is the worker's *actual* lossy state, that delta already
re-carries every bit of mass past dispatches dropped — the scheme is
self-correcting, and adding the residual on top would count the deficit
twice per dispatch and diverge.  For the EF codecs the downlink
``down_residual`` is the encode's *output* (``x - recon`` = the worker's
post-fetch deficit): real error-feedback memory for accounting and
tests, never re-added to the input; non-EF codecs (``delta``/``int8``)
carry no residual memory in either direction, per ``CodecSpec.ef``.

Auto mode may switch a link's uplink codec between dispatches, so the EF
residual must survive the seams: a ``delta``/``int8`` dispatch folds any
carried residual into its encoded delta (``delta`` delivers it exactly,
``int8`` up to quantisation — then the memory ends, per non-EF
semantics), while a ``raw`` dispatch ships absolute weights that cannot
carry residual mass, so the residual is simply kept for the next
compressed dispatch.  Each such seam snapshots the pre-encode residual
per payload, so a cancelled dispatch restores the carried mass exactly
(``restore_uplink``).  With a fixed codec none of this triggers: the
residual is ``None`` on non-EF codecs and the fold is the identity.

Downlink ack protocol.  A delta downlink is only decodable if the worker
still holds the base it was encoded against, so each :class:`Link` tracks
``acked_base`` — the last flat buffer the server *knows* the worker holds.
The ack advances at the worker's fetch-completion event (in a real
deployment this piggybacks on the train response; the explicit event is
what keeps the ack correct for workers that die mid-round, after fetching
but before responding).  A dispatch to a worker with no acked base yet
falls back to the full raw model.  Cancelled or mid-fetch-death fetches
must NOT advance the ack, and they roll the downlink EF residual back to
its pre-encode value: unlike the uplink (where a cancelled response's mass
is gone unless credited back, because the next dispatch re-bases the
worker), the next *downlink* delta ``model - acked_base`` already contains
everything the cancelled dispatch carried — crediting the reconstruction
back would double-count it.

Decode on either side goes straight to a packed flat vector (``base +
dequantised delta`` fused in one pass, the ``FlatServerState``-style
dequantise+delta-apply) — no pytree intermediate on the fast path.

Sharded substrate.  ``Transport(mesh=...)`` resolves the SAME mesh-aware
``ParamBundle`` the server's ``FlatServerState`` uses, so every packed
vector a link touches (``tx_base``, ``acked_base``, EF residuals, decoded
payloads) carries the 1-D ``agg`` ``NamedSharding`` — links encode and
decode against shard-local slices, and decoded responses land in the
server's shard-local rows without any host ever holding the full buffer.

Multi-server links.  In a multi-aggregator topology several servers
dispatch down *one* worker's physical channel, but the worker holds ONE
model — so the downlink ack state is per-WORKER, not per-link.  Passing a
shared :class:`WorkerAckRegistry` to each server's ``Transport`` makes
every link to the same worker encode deltas against one shared
``acked_base``.  The per-link pending dispatch remembers the exact base
it encoded against (a concurrent peer may advance the shared ack before
our fetch completes), and the shared downlink EF residual keeps a revert
CHAIN of in-flight encodes: a cancelled fetch unlinks its own record —
reverting a peer's entry would double-count its deficit — so any
interleaving of cancels and completions restores exact pre-encode values
(property-tested in tests/test_wire_properties.py).

Server<->server links.  The hierarchical topology layer
(``core/topology.py``) reuses this registry unchanged for its leaf<->root
channels: the ROOT aggregator owns a :class:`Transport` whose "workers"
are leaf servers.  The codec table above applies verbatim with the roles
re-cast — a leaf *push* is the uplink (delta vs ``tx_base``, the global
model the leaf last installed; uplink EF residual per leaf link), a root
*fan-out* is the downlink (delta vs ``acked_base``, the last global the
root knows the leaf holds; raw first-contact provision, ack advanced at
the leaf's fetch-complete, downlink EF = the encode output).  A leaf
server dying mid-transfer takes the same restore paths a worker death
does (``restore_uplink`` / ``restore_downlink``), so hierarchical fault
accounting inherits the single-tier proofs.

Unreliable links.  Attaching a :class:`LinkReliability` to a transport
(``runtime/faults.py`` injects one per tier) routes every transfer
through :func:`transmit` — a seeded, deterministic lossy channel with a
retransmit protocol.  Each logical payload gets a per-link sequence
number; each transmitted copy independently drops (``drop_p``) or
duplicates (``dup_p``); the receiver dedups by sequence number, so a
duplicate or a late retransmitted copy is discarded BEFORE it touches
decode state, EF residuals, or byte counters; the sender re-sends on an
ack timeout with exponential backoff, priced off the estimator's
measured bandwidth (``Transport.rel_estimator``) when one is bound, the
actual transmit time otherwise.  A retransmit re-sends the SAME
:class:`Payload` object — byte-identical, never re-encoded — so the EF
books are debited exactly once per logical payload no matter the loss
schedule, and the acked-base invariants above hold bit-exactly
(property-tested in tests/test_wire_properties.py).  Retransmits are
counted on ``Transport.total_retransmits`` (surfaced per history point
as ``HistoryPoint.retransmits``), never in ``up_bytes``/``down_bytes``:
the byte counters remain "delivered payload bytes", which is what the
chaos auditor closes the ledger against.  With ``reliability=None``
(the default) :func:`transmit` degenerates to a single scheduled
delivery event — bit-identical event order to the loss-free simulation.

Lazy link lifecycle.  Links materialize on first contact only —
``Transport.link(wid)`` creates the :class:`Link` the first time a
server actually dispatches to (or hears from) a worker, so a
massive-scale population (``run_fl(cohort=...)``) holds link state for
workers that have ever been in a cohort, never all W.  Under cohort
mode the server additionally bounds RESIDENT links: after each
aggregate it calls :meth:`Transport.lru_evict`, which drops the
least-recently-used QUIESCENT links (never one in the server's keep
set — outstanding requests, claimed merge-window rows, busy workers —
and never one with an un-acked pending downlink) down to
``max_resident_links``.  Evicting a link discards its codec state; on
re-contact ``link()`` builds a fresh one and correctness degrades
gracefully rather than breaking:

  * private ack state (no registry): the fresh link has no
    ``acked_base``, so the next dispatch takes the documented raw
    first-contact fallback — more bytes, same bits;
  * shared :class:`WorkerAckRegistry`: the ack state lives in the
    registry, not the link, so it SURVIVES eviction and the next
    dispatch resumes delta encoding against the worker's true base;
  * uplink EF residual: dropped with the link.  That loses pending
    error-feedback mass exactly as a worker death does (the books
    record it; the chaos auditor's EF-closure invariant only inspects
    resident links), which is why ``lru_evict`` prefers long-idle
    links — their residual is stale speculation about a worker the
    selector stopped picking.

Eviction counts land on ``Transport.total_link_evictions``.
"""
from __future__ import annotations

import functools
import zlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import topk_quant
from repro.runtime import trace

from . import flatbuf

# tie-guard: a kth-largest |x| of exactly 0 (e.g. an all-zero delta from a
# data-less worker) must select nothing, not everything
_THRESH_FLOOR = 1e-30


@dataclass(frozen=True)
class CodecSpec:
    """Static description of one codec: which stages apply."""
    name: str
    delta: bool          # encodes (new - base) instead of absolute weights
    topk: bool           # top-k sparsification (adds the bitmap term)
    quantize: bool       # int8 payload values (adds one f32 scale)
    ef: bool             # error feedback: per-link residual memory


CODECS: Dict[str, CodecSpec] = {
    "raw": CodecSpec("raw", delta=False, topk=False, quantize=False, ef=False),
    "delta": CodecSpec("delta", delta=True, topk=False, quantize=False,
                       ef=False),
    "int8": CodecSpec("int8", delta=True, topk=False, quantize=True,
                      ef=False),
    "topk_ef": CodecSpec("topk_ef", delta=True, topk=True, quantize=False,
                         ef=True),
    "topk_ef+int8": CodecSpec("topk_ef+int8", delta=True, topk=True,
                              quantize=True, ef=True),
}

# the ``auto`` direction-level pseudo-spec: a transport configured auto
# must provision for the most stateful codec its tuner can resolve to —
# packed tx_base, downlink ack protocol, EF residuals — so every
# capability flag is True.  Deliberately NOT in CODECS: no payload ever
# travels as "auto"; encode resolves a concrete registry row per dispatch
# and decode reads the spec off the payload.
AUTO_SPEC = CodecSpec("auto", delta=True, topk=True, quantize=True, ef=True)


@dataclass(slots=True)
class Payload:
    """Envelope for one wire transfer: codec-specific device data plus the
    exact number of bytes the transfer costs on the link.  Slotted: a
    massive-scale round allocates one of these per transfer, and the
    slot layout drops the per-instance dict (measured in
    ``benchmarks/scale_bench.py``)."""
    codec: str
    wire_bytes: int
    data: object


def bitmap_bytes(n_params: int) -> int:
    return (n_params + 7) // 8


def topk_k(n_params: int, frac: float) -> int:
    return max(1, int(n_params * frac))


def expected_codec_bytes(spec: CodecSpec, n_params: int, raw_bytes: int,
                         frac: float) -> int:
    """Steady-state per-transfer bytes of one codec from its spec (top-k
    codecs: assumes exactly k survivors)."""
    if not spec.delta:
        return raw_bytes
    if spec.topk:
        k = topk_k(n_params, frac)
        itemsize = 1 if spec.quantize else 4
        return (bitmap_bytes(n_params) + (4 if spec.quantize else 0)
                + k * itemsize)
    if spec.quantize:
        return n_params + 4
    return 4 * n_params


# exact top-k below this many params; above it, a full-vector top_k/sort
# costs hundreds of ms on CPU (O(n log n) single-threaded), so the
# threshold comes from a deterministic strided sample instead — the DGC
# (Deep Gradient Compression) trick: kept count lands within sampling
# error of k, the wire accounting always counts what actually survived,
# and error feedback recovers anything a slightly-high threshold dropped
_SAMPLE_CAP = 1 << 17


@functools.partial(jax.jit, static_argnames=("k",))
def _topk_thresh_exact(x, k: int):
    return jnp.maximum(jax.lax.top_k(jnp.abs(x), k)[0][-1], _THRESH_FLOOR)


@functools.partial(jax.jit, static_argnames=("ks", "stride"))
def _topk_thresh_sampled(x, ks: int, stride: int):
    s = jnp.abs(x[::stride])
    return jnp.maximum(jnp.sort(s)[-ks], _THRESH_FLOOR)


def topk_threshold(x, k: int, n_params: int):
    """|x| threshold selecting ~the k largest coordinates (exact for small
    vectors, sampled above _SAMPLE_CAP)."""
    if n_params <= _SAMPLE_CAP:
        return _topk_thresh_exact(x, k)
    P = int(x.shape[0])
    stride = max(1, P // _SAMPLE_CAP)
    m = (P + stride - 1) // stride
    ks = min(m, max(1, round(m * k / n_params)))
    return _topk_thresh_sampled(x, ks, stride)


@jax.jit
def _int8_scale(x):
    return jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0


@jax.jit
def _kept_count(x, thresh):
    return jnp.sum(jnp.abs(x) >= thresh, dtype=jnp.int32)


@jax.jit
def _mask_encode(x, thresh):
    """Top-k sparsify without quantisation: (recon, residual)."""
    recon = jnp.where(jnp.abs(x) >= thresh, x, 0.0)
    return recon, x - recon


@jax.jit
def _dequant(q, scale):
    return q.astype(jnp.float32) * scale


def ef_topk_encode(x: jnp.ndarray, *, n_params: int, frac: float,
                   quantize: bool, use_pallas=None, interpret=None
                   ) -> Tuple[object, jnp.ndarray, jnp.ndarray, int]:
    """Flat-vector EF top-k(+int8) encode: one fused pass over ``x`` (=
    delta + residual).  Returns ``(data, recon, residual, wire_bytes)``
    where ``data`` is what travels ((q, scale) or the dense sparsified
    vector), ``recon`` the receiver-visible reconstruction, ``residual``
    the new error-feedback memory, and ``wire_bytes`` the exact cost per
    the codec table."""
    thresh = topk_threshold(x, topk_k(n_params, frac), n_params)
    with trace.span("fl.sync", site="kept_count"):
        kept = int(_kept_count(x, thresh))
    trace.count("host_syncs")
    if quantize:
        scale = _int8_scale(x)
        q, resid = topk_quant.topk_quant_encode(x, thresh, scale,
                                                use_pallas=use_pallas,
                                                interpret=interpret)
        wire = bitmap_bytes(n_params) + 4 + kept
        return (q, scale), _dequant(q, scale), resid, wire
    recon, resid = _mask_encode(x, thresh)
    wire = bitmap_bytes(n_params) + 4 * kept
    return recon, recon, resid, wire


class WorkerAckState:
    """One worker's downlink ack state: the last flat buffer any server
    knows the worker holds, plus the worker's downlink EF residual.

    The residual is speculative while dispatches are in flight — each
    delta encode overwrites it assuming delivery.  ``_entries`` is the
    revert chain: one ``[residual-before-encode, residual-this-encode-
    wrote]`` record per in-flight encode, in encode order, so any
    interleaving of cancels and completions across concurrent
    (multi-server) dispatches leaves the residual at the deficit of the
    dispatch the worker actually holds — cancelling the newest encode
    reverts the residual itself, cancelling an older one re-points its
    successor's revert target past it, and a delivery re-bases every
    still-in-flight OLDER encode on the state it established (and, when
    nothing newer is in flight, installs its own deficit: concurrent
    fetches may complete out of encode order)."""

    __slots__ = ("acked_base", "down_residual", "_entries")

    def __init__(self):
        self.acked_base: Optional[jnp.ndarray] = None
        self.down_residual: Optional[jnp.ndarray] = None
        self._entries: list = []

    def push(self) -> list:
        e = [self.down_residual, None]    # [res_before, resid_self]
        self._entries.append(e)
        return e

    def _index(self, entry) -> int:
        for i, e in enumerate(self._entries):
            if e is entry:
                return i
        return -1

    def complete(self, entry) -> None:
        """``entry``'s dispatch was delivered: the worker now holds its
        reconstruction, so older in-flight encodes revert to the deficit
        it established, and — unless a newer encode is still speculating
        on top — so does the live residual (out-of-order completions:
        the LAST delivery wins, whatever the encode order)."""
        i = self._index(entry)
        if i < 0:
            return
        for e in self._entries[:i]:
            e[0] = entry[1]
        newest = i == len(self._entries) - 1
        self._entries.pop(i)
        if newest:
            self.down_residual = entry[1]

    def cancel(self, entry) -> None:
        """``entry``'s dispatch was never delivered: unlink it from the
        revert chain.  The newest entry owns the live residual, so
        cancelling it reverts the residual itself; cancelling an older
        entry re-points its successor's revert target past it."""
        i = self._index(entry)
        if i < 0:
            return
        self._entries.pop(i)
        if i == len(self._entries):              # was the newest encode
            self.down_residual = entry[0]
        else:
            self._entries[i][0] = entry[0]


class WorkerAckRegistry:
    """Shared per-worker ack state for multi-server topologies: hand ONE
    registry to every server's ``Transport`` and all their links to the
    same worker share one ``acked_base`` — each server's downlink delta
    encodes against the worker's actual state, whichever server last
    delivered it."""

    def __init__(self):
        self._states: Dict[str, WorkerAckState] = {}

    def state(self, worker_id: str) -> WorkerAckState:
        st = self._states.get(worker_id)
        if st is None:
            st = self._states[worker_id] = WorkerAckState()
        return st


@dataclass(frozen=True)
class LinkReliability:
    """Seeded per-link loss model + retransmit policy.

    Each transmitted copy of a payload independently never arrives with
    probability ``drop_p`` and is delivered twice (the duplicate arriving
    late, at ``dup_delay * t_tx``) with probability ``dup_p``.  The sender
    retransmits the SAME payload object after ``timeout_mult`` times the
    estimated one-way time, backing off by ``backoff`` per attempt, up to
    ``max_attempts`` total copies.  All randomness comes from a
    per-(link, seed) ``RandomState``, so a given (topology, schedule,
    seed) triple replays bit-exactly."""
    drop_p: float = 0.0
    dup_p: float = 0.0
    seed: int = 0
    timeout_mult: float = 3.0
    backoff: float = 2.0
    max_attempts: int = 64
    dup_delay: float = 2.0


@dataclass
class TransportAudit:
    """Delivery ledger for one transport's lossy links — the raw material
    the chaos auditor (``runtime/faults.audit_chaos_run``) closes the
    books against.  Only :func:`transmit` writes it (so it sees exactly
    the wire), plus the fetch log noted by receivers at fetch time.

    ``sent_bytes[dir]`` counts ORIGINAL sends only (attempt 0);
    retransmitted copies land in ``retx_count``/``retx_bytes``; a
    deduplicated (second/late) arrival lands in ``dup_count`` and nowhere
    else.  Since servers count up/down bytes per delivered-and-accepted
    payload, the closing inequalities are
    ``counted_up <= delivered_bytes["up"]`` and
    ``sent_bytes["down"] <= counted_down`` per transport."""
    sent_bytes: Dict[str, int] = field(
        default_factory=lambda: {"up": 0, "down": 0})
    sent_count: Dict[str, int] = field(
        default_factory=lambda: {"up": 0, "down": 0})
    delivered_bytes: Dict[str, int] = field(
        default_factory=lambda: {"up": 0, "down": 0})
    delivered_count: Dict[str, int] = field(
        default_factory=lambda: {"up": 0, "down": 0})
    dup_count: Dict[str, int] = field(
        default_factory=lambda: {"up": 0, "down": 0})
    retx_count: int = 0
    retx_bytes: int = 0
    # receiver-side fetch log: worker/leaf id -> model versions fetched,
    # in fetch-completion order (the monotone-version invariant's input)
    fetch_versions: Dict[str, List[int]] = field(default_factory=dict)

    def note_sent(self, direction: str, nbytes: int, retransmit: bool):
        if retransmit:
            self.retx_count += 1
            self.retx_bytes += nbytes
        else:
            self.sent_bytes[direction] += nbytes
            self.sent_count[direction] += 1

    def note_delivered(self, direction: str, nbytes: int):
        self.delivered_bytes[direction] += nbytes
        self.delivered_count[direction] += 1

    def note_dup(self, direction: str):
        self.dup_count[direction] += 1

    def note_fetch(self, worker_id: str, version: int):
        self.fetch_versions.setdefault(worker_id, []).append(version)


class _Channel:
    """Per-link lossy-channel state: the seeded RNG, the per-payload
    sequence counter, and the receiver's delivered-set (never pruned, so
    arbitrarily late duplicates still dedup)."""

    __slots__ = ("rng", "_seq", "delivered")

    def __init__(self, seed: int):
        self.rng = np.random.RandomState(seed & 0xFFFFFFFF)
        self._seq = 0
        self.delivered: set = set()

    def next_seq(self) -> int:
        self._seq += 1
        return self._seq


def transmit(loop, link: "Link", payload: Payload, t_tx: float,
             deliver, direction: str = "up"):
    """Send ``payload`` over ``link``, invoking ``deliver`` exactly once
    when the first copy arrives.

    With no reliability model this is exactly ``loop.schedule(t_tx,
    deliver)`` — one event, bit-identical to the loss-free simulation.
    With one, the payload gets a sequence number and rides the lossy
    channel: dropped copies trigger an ack-timeout retransmit of the SAME
    payload object with exponential backoff (``Transport.
    total_retransmits`` counts them); duplicate/late copies are dropped by
    the receiver's sequence dedup before they can touch decode state, EF
    residuals, or byte counters.

    Returns the scheduled delivery :class:`_Event` on the reliable
    (single-event) paths so a snapshot can serialize the in-flight leg
    exactly; lossy paths return ``None`` (their in-flight legs are
    cancelled-with-credit at snapshot instead)."""
    rel = link.reliability
    if rel is None:
        aud = link.t.audit
        if aud is None:
            return loop.schedule(t_tx, deliver)
        # reliable link on an audited transport (e.g. the promoted root's
        # loopback after failover): same single event, but the delivery
        # ledger still books the transfer so the chaos auditor closes
        aud.note_sent(direction, payload.wire_bytes, False)

        def _deliver_booked():
            aud.note_delivered(direction, payload.wire_bytes)
            deliver()
        return loop.schedule(t_tx, _deliver_booked)
    t = link.t
    aud = t.audit
    ch = link.channel()
    seq = ch.next_seq()
    # the pending ack-timeout handle: the first delivery cancels it, so a
    # large lossy fleet's heap holds live timers for IN-FLIGHT payloads
    # only, not one dead entry per delivered payload (the cancelled event
    # never fires, which is exactly what the `seq in ch.delivered` guard
    # made it do — event-order identical, minus the no-op pops)
    timer = [None]

    def _arrive():
        if seq in ch.delivered:          # duplicate or late retransmit:
            if aud is not None:          # dropped before ANY codec state
                aud.note_dup(direction)
            return
        ch.delivered.add(seq)            # doubles as the (instant) ack
        if timer[0] is not None:
            loop.cancel(timer[0])
            timer[0] = None
        if aud is not None:
            aud.note_delivered(direction, payload.wire_bytes)
        deliver()

    def _send(attempt: int):
        if aud is not None:
            aud.note_sent(direction, payload.wire_bytes, attempt > 0)
        if attempt > 0:
            t.total_retransmits += 1
        dropped = ch.rng.random_sample() < rel.drop_p
        duped = ch.rng.random_sample() < rel.dup_p
        if not dropped:
            loop.schedule(t_tx, _arrive)
            if duped:                    # network-level duplicate, late
                loop.schedule(rel.dup_delay * t_tx, _arrive)
        if attempt + 1 < rel.max_attempts:
            timer[0] = loop.schedule(
                link.rto(payload.wire_bytes, t_tx, attempt),
                lambda: _check(attempt))

    def _check(attempt: int):
        timer[0] = None
        if seq in ch.delivered or t.closed:   # acked, or the sender died
            return                            # — retransmit timer dies
        _send(attempt + 1)

    _send(0)
    return None


def resume_transmit(loop, link: "Link", payload: Payload, t_abs: float,
                    deliver, direction: str = "up"):
    """Re-create a reliable-path delivery event whose *send* was already
    booked before a snapshot: the audit (if any) books only the delivery —
    calling :func:`transmit` again would double-count the send.  Lossy
    legs are never resumed this way (they are cancelled-with-credit at
    snapshot and re-dispatched fresh).  ``t_abs`` is the serialized
    absolute deadline, replayed exactly via ``schedule_abs`` so the
    resumed run stays bit-identical to the uninterrupted one."""
    aud = link.t.audit
    if link.reliability is None and aud is not None:
        def _deliver_booked():
            aud.note_delivered(direction, payload.wire_bytes)
            deliver()
        return loop.schedule_abs(t_abs, _deliver_booked)
    return loop.schedule_abs(t_abs, deliver)


# sentinel: "no per-link override — inherit the transport's reliability"
_REL_INHERIT = object()


class Link:
    """One server<->worker channel: per-link codec state.

    ``tx_base`` is the packed model the worker fetched on the most recent
    dispatch down this link — for a raw downlink the packed server model,
    for a compressed downlink the (lossy) reconstruction — i.e. the base
    every *uplink* delta encodes against and decodes onto.  ``acked_base``
    is the last flat buffer the server knows the worker holds: the base
    every *downlink* delta encodes against (advanced only by
    :meth:`ack_down` at fetch completion).  Each direction carries its own
    error-feedback residual.  Both endpoints of the simulated channel
    share the object, mirroring the thesis' dedicated FTP weight channel.

    The downlink state (``acked_base``/``down_residual``) lives in a
    :class:`WorkerAckState` — private per link by default, shared across
    servers when the transports were built with one
    :class:`WorkerAckRegistry`.

    Slotted for massive-scale populations (one Link per contacted
    worker); the ``__dict__`` slot keeps the instance dict availably
    lazy — it costs one pointer until something (a test spy, say)
    actually assigns an ad-hoc attribute.
    """

    __slots__ = ("t", "worker_id", "tx_base", "residual", "_ack",
                 "_pending_down", "_up_restore", "_reliability", "_chan",
                 "__dict__", "__weakref__")

    def __init__(self, transport: "Transport",
                 ack: Optional[WorkerAckState] = None,
                 worker_id: str = ""):
        self.t = transport
        self.worker_id = worker_id
        self.tx_base: Optional[jnp.ndarray] = None   # packed dispatch base
        self.residual: Optional[jnp.ndarray] = None  # uplink EF (topk_ef*)
        self._ack = ack if ack is not None else WorkerAckState()
        # in-flight downlink awaiting ack:
        # (payload, revert-chain entry or None, pinned encode base or None)
        self._pending_down: Optional[tuple] = None
        # auto-mode codec seam: (payload, pre-encode residual) of the last
        # uplink encode that folded/parked carried EF mass, so a cancel
        # can restore exactly what the seam consumed
        self._up_restore: Optional[tuple] = None
        self._reliability = _REL_INHERIT   # per-link override (loopbacks)
        self._chan: Optional[_Channel] = None

    # --- lossy-channel state ---
    @property
    def reliability(self) -> Optional[LinkReliability]:
        r = self._reliability
        return self.t.reliability if r is _REL_INHERIT else r

    @reliability.setter
    def reliability(self, value: Optional[LinkReliability]):
        self._reliability = value

    def channel(self) -> _Channel:
        ch = self._chan
        if ch is None:
            # crc32, not hash(): per-process hash randomisation would
            # break the seeded-replay guarantee
            mix = (zlib.crc32(self.worker_id.encode())
                   ^ (self.reliability.seed * 2654435761)) & 0xFFFFFFFF
            ch = self._chan = _Channel(mix)
        return ch

    def rto(self, wire_bytes: int, t_tx: float, attempt: int) -> float:
        """Retransmit timeout for copy ``attempt``: ``timeout_mult`` times
        the estimated one-way time — the estimator's measured bandwidth
        when the transport has one bound (``rel_estimator``), the actual
        transmit time otherwise — with exponential backoff."""
        rel = self.reliability
        base = t_tx
        est = self.t.rel_estimator
        if est is not None and self.worker_id:
            bw = est.bandwidth(self.worker_id)
            if bw:
                base = wire_bytes / bw
        return rel.timeout_mult * max(base, t_tx) * rel.backoff ** attempt

    @property
    def acked_base(self) -> Optional[jnp.ndarray]:
        return self._ack.acked_base

    @property
    def down_residual(self) -> Optional[jnp.ndarray]:
        return self._ack.down_residual

    # --- shared flat-delta codec stages ---
    def _codec_encode(self, delta: jnp.ndarray, residual, spec: CodecSpec,
                      frac: Optional[float] = None) -> Tuple[Payload, object]:
        """Encode one packed flat delta through ``spec`` at sparsity
        ``frac`` (the transport's configured frac when None); returns
        ``(payload, new_residual)``.  A carried residual folds into the
        encoded quantity for every delta codec — for non-EF specs that
        only happens at an auto-mode codec seam (fixed non-EF codecs
        never hold one), and the returned residual is then the caller's
        to clear: ``delta`` delivered the mass exactly, ``int8`` up to
        quantisation, and non-EF codecs keep no memory of the deficit."""
        t = self.t
        n = t.bundle.n_params
        if frac is None:
            frac = t.frac
        if spec.topk:
            if residual is None:
                residual = jnp.zeros_like(delta)
            x = delta + residual
            data, _, resid, wire = ef_topk_encode(
                x, n_params=n, frac=frac, quantize=spec.quantize,
                use_pallas=t.use_pallas, interpret=t.interpret)
            return Payload(spec.name, wire, data), \
                (resid if spec.ef else residual)
        x = delta if residual is None else delta + residual
        if spec.quantize:                        # int8: whole delta
            scale = _int8_scale(x)
            q, _ = topk_quant.topk_quant_encode(
                x, 0.0, scale, use_pallas=t.use_pallas,
                interpret=t.interpret)
            return Payload(spec.name, n + 4, (q, scale)), residual
        return Payload(spec.name, 4 * n, x), residual  # dense f32

    def _codec_apply(self, data, spec: CodecSpec,
                     base: jnp.ndarray) -> jnp.ndarray:
        """``base + recon(delta)`` — the fused dequantise+delta-apply."""
        if spec.quantize:
            q, scale = data
            # fused dequantise + delta-apply: one pass, no f32 intermediate
            return topk_quant.dequant_add(q, scale, base,
                                          use_pallas=self.t.use_pallas,
                                          interpret=self.t.interpret)
        return base + data

    # --- downlink: server -> worker ---
    @property
    def needs_down_ack(self) -> bool:
        """True when the downlink codec is stateful (delta vs acked base),
        so fetch completion must be signalled explicitly."""
        return self.t.spec_down.delta

    def encode_down(self, weights_tree) -> Payload:
        with trace.span("fl.encode_down", worker=self.worker_id):
            t = self.t
            sd, frac = t.resolve_down(self)
            if not sd.delta:
                if t.tracks_tx_base:
                    # remember the packed base so the uplink delta decodes
                    self.tx_base = t._pack_down(weights_tree)
                payload = Payload("raw", t.raw_bytes, weights_tree)
                if t.auto_down:
                    # an auto-resolved raw dispatch (warmup, backbone, or an
                    # unmeasured link) still rides the ack machinery: the
                    # fetch-complete ack establishes the base later delta
                    # dispatches encode against (touches no residual, so it
                    # joins no revert chain)
                    self._pending_down = (payload, None, None)
                return payload
            vec = t._pack_down(weights_tree)
            if self.acked_base is None:
                # first dispatch: the worker holds no base yet -> raw fallback
                # (touches no residual, so it joins no revert chain)
                self.tx_base = vec
                payload = Payload("raw", t.raw_bytes, weights_tree)
                self._pending_down = (payload, None, None)
                return payload
            # the delta vs the worker's ACTUAL (acked) state is already the
            # error-feedback-corrected quantity: it re-carries every bit of
            # mass past dispatches dropped, so nothing is added on top — an
            # explicit residual term here would count that deficit twice per
            # dispatch and diverge.  For EF codecs _codec_encode still emits
            # the residual OUTPUT (x - recon = the worker's post-fetch
            # deficit), the genuine per-link downlink EF memory.
            base = self.acked_base
            delta = vec - base
            entry = self._ack.push()             # joins the revert chain
            payload, new_res = self._codec_encode(delta, None, sd, frac)
            self._ack.down_residual = entry[1] = new_res
            # the worker-visible model after this fetch (== what decode_down
            # produces, same fused op on the same inputs): the uplink base
            self.tx_base = self._codec_apply(payload.data, sd, base)
            # the pending entry pins the encode-time base: a multi-server peer
            # may advance the shared ack before this fetch completes, and the
            # delta only decodes against the base it was cut from
            self._pending_down = (payload, entry, base)
            return payload

    def decode_down_vec(self, payload: Payload) -> jnp.ndarray:
        """Payload -> packed flat f32 vector of the dispatched model,
        reconstructed against the base it was encoded from (the pending
        dispatch's pinned base; the link's acked base otherwise)."""
        if payload.codec == "raw":
            return self.t._pack_down(payload.data)
        base = self.acked_base
        if (self._pending_down is not None
                and self._pending_down[0] is payload
                and self._pending_down[2] is not None):
            base = self._pending_down[2]
        # the payload names the codec it was actually encoded with — under
        # auto the link default is a pseudo-spec and dispatches interleave
        # concrete codecs, so decode must never assume the link default
        return self._codec_apply(payload.data, CODECS[payload.codec], base)

    def decode_down(self, payload: Payload):
        """Payload -> weight pytree (no ack bookkeeping — raw downlinks
        and reference paths)."""
        if payload.codec == "raw":
            return payload.data
        return self.t.bundle.unpack(self.decode_down_vec(payload))

    def ack_down(self, payload: Payload, vec: jnp.ndarray) -> None:
        """Advance the last-acked state to ``vec`` (the decoded model) —
        the fetch-complete event.  Only the payload that is actually
        pending may ack: a stale or already-cancelled fetch must not
        advance the ack (a raw payload with nothing pending is allowed —
        re-acking a full model the worker genuinely received is exact)."""
        entry = None
        if self._pending_down is not None:
            if self._pending_down[0] is not payload:
                return               # stale fetch: not the pending dispatch
            entry = self._pending_down[1]
        elif payload.codec != "raw":
            return                   # delta payload already acked/cancelled
        self._ack.acked_base = vec
        self._pending_down = None
        if entry is not None:
            self._ack.complete(entry)

    def complete_fetch(self, payload: Payload):
        """Worker-side fetch completion: decode against the local acked
        base, advance the ack, return the weight pytree to train from.

        For the pending dispatch the reconstruction was already computed
        at encode time (``tx_base`` — the same fused op on the same
        inputs), so the shared simulated channel reuses it instead of
        re-running the kernel; :meth:`decode_down_vec` remains the
        wire-honest path, bit-parity-asserted in the transport tests."""
        pending = (self._pending_down is not None
                   and self._pending_down[0] is payload)
        vec = self.tx_base if pending else self.decode_down_vec(payload)
        self.ack_down(payload, vec)
        if payload.codec == "raw":
            return payload.data
        return self.t.bundle.unpack(vec)

    def restore_downlink(self, payload: Payload) -> None:
        """Roll back a never-delivered downlink (cancelled fetch or death
        mid-fetch): the ack has not advanced, so the next dispatch's delta
        ``model - acked_base`` already re-carries this payload's mass —
        the EF residual must revert to its pre-encode value (crediting the
        reconstruction back, as the uplink does, would double-count).

        Shared-ack (multi-server) links revert through the chain: if a
        peer encoded after us, the live residual is the peer's accounting
        entry and its own delta vs the (unchanged) acked base re-carries
        our mass — so our record is unlinked from the chain instead of
        clobbering the peer's value (reverting it would double-count)."""
        if self._pending_down is None or self._pending_down[0] is not payload:
            return
        _, entry, _base = self._pending_down
        self._pending_down = None
        if entry is not None:
            self._ack.cancel(entry)

    # --- uplink: worker -> server (codec'd response) ---
    def upfront_up_bytes(self) -> Optional[int]:
        """Exact uplink cost known before training, or None when the size is
        data-dependent (top-k codecs: ``kept`` varies with threshold ties;
        auto: the codec itself is resolved at encode time)."""
        spec = self.t.spec_up
        if spec.topk:
            return None
        return self.t.expected_up_bytes()

    def encode_up(self, new_tree) -> Payload:
        with trace.span("fl.encode_up", worker=self.worker_id):
            spec, frac = self.t.resolve_up(self)
            if not spec.delta:                   # raw: ship the tree as-is
                payload = Payload(spec.name, self.t.raw_bytes, new_tree)
                if self.t.auto_up:
                    # raw ships absolute weights and cannot carry EF mass:
                    # park the residual for the next compressed dispatch
                    # (nothing consumed, so nothing to snapshot)
                    self._up_restore = None
                return payload
            vec = self.t.bundle.pack(new_tree)
            prev_res = self.residual
            payload, self.residual = self._codec_encode(
                vec - self.tx_base, prev_res, spec, frac)
            if self.t.auto_up:
                if not spec.ef and prev_res is not None:
                    # auto codec seam: the carried residual was folded into
                    # this exact/quantised delta, so the memory ends here —
                    # snapshot it so a cancelled dispatch restores the mass
                    self._up_restore = (payload, prev_res)
                    self.residual = None
                else:
                    self._up_restore = None
            return payload

    def decode_up_vec(self, payload: Payload) -> jnp.ndarray:
        """Payload -> packed flat f32 vector of the worker's new absolute
        weights (lands directly in the server's (W, N) row buffer).  The
        spec comes off the payload: under auto the link default is a
        pseudo-spec and dispatches interleave concrete codecs."""
        spec = CODECS[payload.codec]
        if not spec.delta:
            return self.t.bundle.pack(payload.data)
        return self._codec_apply(payload.data, spec, self.tx_base)

    def restore_uplink(self, payload: Payload) -> None:
        """Credit a never-applied uplink's mass back into the EF residual:
        encode debits the residual assuming delivery, so a transfer that is
        cancelled mid-transmit or discarded by the receiver (sync staleness)
        must put its reconstruction back, or that top-k mass is silently
        lost from both the model and the error-feedback memory.  (The next
        dispatch re-bases the worker, so — unlike a cancelled downlink —
        nothing else re-carries this mass.)

        The spec is the PAYLOAD's: an auto link may have encoded this
        dispatch with a different codec than its next one.  A cancelled
        non-EF dispatch that folded carried residual at an auto codec seam
        restores the pre-encode snapshot instead (the folded-in mass would
        otherwise vanish with the cancelled payload)."""
        spec = CODECS[payload.codec]
        if self._up_restore is not None and self._up_restore[0] is payload:
            restore = self._up_restore[1]
            self._up_restore = None
            if not spec.ef:
                self.residual = restore if self.residual is None \
                    else self.residual + restore
                return
        if not spec.ef:
            return
        data = payload.data
        recon = _dequant(*data) if spec.quantize else data
        self.residual = recon if self.residual is None \
            else self.residual + recon


class Transport:
    """Codec registry instance + per-worker links for one server.

    ``codec`` names the uplink codec; ``down_codec`` the downlink one
    (``None`` = symmetric, i.e. the same codec both ways; pass ``"raw"``
    for the PR-2-era uplink-only compression).  ``raw_bytes`` (the wire size
    of a raw transfer) defaults to the template's native byte size; pass
    the server's ``model_bytes`` to pin it.  The template must pack
    (``flatbuf.bundle_for`` rejects it otherwise).  ``mesh`` (the server's 1-D ``agg`` mesh) resolves the
    mesh-aware bundle, so links hold and codec shard-local slices;
    ``ack_registry`` shares per-worker downlink ack state across servers
    (multi-aggregator topologies).
    """

    def __init__(self, template, codec: str = "raw", *,
                 down_codec: Optional[str] = None, frac: float = 0.1,
                 raw_bytes: Optional[int] = None, use_pallas=None,
                 interpret=None, mesh=None,
                 ack_registry: Optional[WorkerAckRegistry] = None,
                 auto_policy=None):
        if down_codec is None:
            down_codec = codec
        for c in (codec, down_codec):
            if c not in CODECS and c != AUTO_SPEC.name:
                raise ValueError(f"unknown codec {c!r}; "
                                 f"have {sorted(CODECS) + [AUTO_SPEC.name]}")
        self.auto_up = codec == AUTO_SPEC.name
        self.auto_down = down_codec == AUTO_SPEC.name
        self.spec_up = AUTO_SPEC if self.auto_up else CODECS[codec]
        self.spec_down = AUTO_SPEC if self.auto_down else CODECS[down_codec]
        self.frac = float(frac)
        # codec stages run inside plain jit, and Pallas calls do NOT
        # auto-partition under GSPMD (only the merge kernels are
        # shard_map'ed) — on a >1-device mesh the codec must take the XLA
        # oracle path, which partitions shard-locally and is the kernels'
        # bit-parity target anyway
        if (mesh is not None and use_pallas is None
                and int(np.prod(mesh.devices.shape)) > 1):
            use_pallas = False
        self.use_pallas = use_pallas
        self.interpret = interpret
        self.mesh = mesh
        self._ack_registry = ack_registry
        self.bundle = flatbuf.bundle_for(template, mesh)
        self.raw_bytes = int(raw_bytes if raw_bytes is not None
                             else self.bundle.raw_bytes)
        # auto mode: the per-link codec/frac resolver (core/autotune.py);
        # bandwidth sources are bound by whoever owns the estimator
        # (experiment.run_fl / topology.build_topology)
        if self.auto_up or self.auto_down:
            from .autotune import AutoTuner
            self.tuner: Optional[object] = AutoTuner(
                self.bundle.n_params, self.raw_bytes, auto_policy)
        else:
            self.tuner = None
        # insertion/access-ordered (dicts preserve order; link() re-inserts
        # on hit), so iteration order IS least-recently-used order — what
        # lru_evict walks
        self._links: Dict[str, Link] = {}
        self.total_link_evictions = 0
        # lossy-channel model (None = perfect wire, the default);
        # runtime/faults injects these per tier
        self.reliability: Optional[LinkReliability] = None
        self.rel_estimator = None     # TimeEstimator pricing retransmit RTOs
        self.total_retransmits = 0
        self.audit: Optional[TransportAudit] = None
        # a dead owner (e.g. a failed-over root) closes its transport:
        # copies already on the wire still arrive, but retransmit timers
        # stop re-sending on the dead process's behalf
        self.closed = False
        # one packed copy of the current server model per dispatch round:
        # every selected worker's encode_down shares it (keyed on tree
        # identity)
        self._down_tree = None
        self._down_vec: Optional[jnp.ndarray] = None

    def _pack_down(self, weights_tree) -> jnp.ndarray:
        if self._down_tree is not weights_tree:
            self._down_vec = self.bundle.pack(weights_tree)
            self._down_tree = weights_tree
        return self._down_vec

    @property
    def codec(self) -> str:
        return self.spec_up.name

    @property
    def down_codec(self) -> str:
        return self.spec_down.name

    @property
    def tracks_tx_base(self) -> bool:
        """True when links carry a packed dispatch base (either direction
        is a delta codec) — i.e. ``link.tx_base`` is the worker's fetched
        model in flat-vector form."""
        return self.spec_up.delta or self.spec_down.delta

    # --- per-dispatch codec resolution (auto mode) ---
    def resolve_up(self, link: "Link") -> Tuple[CodecSpec, float]:
        """The concrete (spec, frac) this link's next uplink encode uses:
        the configured constants, or the tuner's per-link choice."""
        if not self.auto_up:
            return self.spec_up, self.frac
        name, frac = self.tuner.choose(link.worker_id, self._retx_factor())
        return CODECS[name], frac

    def resolve_down(self, link: "Link") -> Tuple[CodecSpec, float]:
        if not self.auto_down:
            return self.spec_down, self.frac
        name, frac = self.tuner.choose(link.worker_id, self._retx_factor())
        return CODECS[name], frac

    def note_round(self, point) -> None:
        """HistoryPoint feedback: one aggregation round closed — advance
        the auto tuner's warmup/plateau schedule.  No-op on fixed-codec
        transports, so every existing call site stays bit-identical."""
        if self.tuner is not None:
            self.tuner.note_round(point.accuracy)

    def link(self, worker_id: str) -> Link:
        l = self._links.get(worker_id)
        if l is None:
            ack = (self._ack_registry.state(worker_id)
                   if self._ack_registry is not None else None)
            l = self._links[worker_id] = Link(self, ack, worker_id)
        else:
            # move-to-end: keep dict order == recency order for lru_evict
            del self._links[worker_id]
            self._links[worker_id] = l
        return l

    def lru_evict(self, keep=(), max_links: Optional[int] = None) -> int:
        """Evict least-recently-used QUIESCENT links until at most
        ``max_links`` remain; returns how many were dropped.

        Only quiescent links are candidates: anything in ``keep`` (the
        server passes its outstanding/busy/windowed workers) or with a
        pending downlink awaiting ack is skipped — evicting those would
        lose in-flight codec state.  See the module docstring ("Lazy link
        lifecycle") for what eviction costs on re-contact."""
        if max_links is None or len(self._links) <= max_links:
            return 0
        evicted = 0
        keep = set(keep)
        for wid in list(self._links):
            if len(self._links) <= max_links:
                break
            l = self._links[wid]
            if wid in keep or l._pending_down is not None:
                continue
            del self._links[wid]
            evicted += 1
        self.total_link_evictions += evicted
        return evicted

    # --- expected costs (selection time budgets / straggler timeouts) ---
    def _retx_factor(self) -> float:
        """Expected transmissions per delivered payload on a lossy link
        (geometric: 1/(1-drop_p)) — scales the selection-pricing byte
        estimates so eq-3.4 time budgets and straggler timeouts price the
        retransmit tax in.  1.0 on a perfect wire, so every existing
        (reliability=None) pricing is untouched."""
        rel = self.reliability
        if rel is None or rel.drop_p <= 0.0:
            return 1.0
        return 1.0 / max(1.0 - rel.drop_p, 1e-3)

    def expected_down_bytes(self) -> int:
        """Per-dispatch downlink estimate from the down codec spec (the
        steady state: first-contact dispatches cost ``raw_bytes``).  Under
        auto the tuner's steady choice prices the *current* rung of its
        schedule — raw while no rate is known or a forced warmup lasts,
        the compressed pick afterwards — so selection's BytesSpec
        callables become time-varying per round."""
        spec, frac = self.spec_down, self.frac
        if self.auto_down:
            name, frac = self.tuner.steady_choice(self._retx_factor())
            spec = CODECS[name]
        return int(expected_codec_bytes(spec, self.bundle.n_params,
                                        self.raw_bytes, frac)
                   * self._retx_factor())

    def expected_up_bytes(self) -> int:
        """Per-response uplink estimate from the codec spec (top-k codecs:
        assumes exactly k survivors); auto mode prices the tuner's current
        steady choice, see :meth:`expected_down_bytes`."""
        spec, frac = self.spec_up, self.frac
        if self.auto_up:
            name, frac = self.tuner.steady_choice(self._retx_factor())
            spec = CODECS[name]
        return int(expected_codec_bytes(spec, self.bundle.n_params,
                                        self.raw_bytes, frac)
                   * self._retx_factor())

    def expected_oneway_bytes(self) -> int:
        """Mean per-direction bytes of a round trip — the figure the
        selection policies plug into the eq-3.4 time budget (for ``raw``
        both ways this is exactly the model's byte size, matching the
        thesis)."""
        return (self.expected_down_bytes() + self.expected_up_bytes()) // 2
