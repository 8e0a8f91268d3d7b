"""Hierarchical multi-server federation orchestration.

The thesis' FogBus2 architecture places multiple containerized aggregation
servers between edge worker pools and the cloud; FLight (arXiv:2308.02834)
and the fog-FL literature make hierarchical re-aggregation the step that
scales an edge federation past one coordinator.  This module builds that
topology out of the existing substrate: several leaf
:class:`~repro.core.server.AggregationServer`\\ s each drive a disjoint
worker pool (own transport links, selection policy, straggler budgets) and
periodically push their merged models up a server<->server link to a ROOT
aggregator, which re-merges the leaf contributions with the SAME fused
flat-buffer pass (``fedavg_mix_flat`` via ``flatbuf.FlatServerState``) and
fans the new global model back down the codec'd downlink path.

Wire discipline.  Server<->server links are ordinary
:class:`~repro.core.transport.Link`\\ s from the root's own
:class:`~repro.core.transport.Transport` — a leaf plays the worker role on
its uplink.  Leaf pushes are codec'd deltas against the global model the
leaf last installed (``tx_base``); root fan-outs are codec'd deltas against
the leaf's last-ACKED global (``acked_base``), with the raw first-contact
fallback, per-link error-feedback residuals, and the revert-chain cancel
semantics all inherited unchanged.  Every payload carries exact
``wire_bytes``; the root's :class:`~repro.core.server.HistoryPoint` byte
counters accumulate exactly the server-link payloads (uplink counted at
arrival, downlink at dispatch — the same convention the worker tier uses),
so the root-merged history's counters equal the sum of per-leaf payload
``wire_bytes``.

Push modes.  ``push="sync"`` barriers: the root merges once every alive
leaf's push has arrived (n_data-weighted across leaves), then fans the new
global to all of them.  ``push="async"`` merges each arriving push
immediately — staleness-weighted (``root_alpha * (1+s)^-root_stale_pow``
damping, staleness in global versions since the leaf's installed base) —
and fans back to the pusher alone, so a fast leaf never waits on a slow
one.  In both modes a leaf HOLDS its worker dispatch between its push and
the fan-out's arrival (``AggregationServer.hold``/``release``): the leaf's
next local rounds always train from the freshest global it can have.

Flat topology.  A ``"1x1"`` topology (one root, one leaf) runs in
*passthrough*: the root is colocated with its only leaf, so there is no
server<->server wire, no hold, and the root's history is the leaf's
verbatim — bit-identical to the single-server path (pinned by the
``*_flat1x1`` golden aliases in tests/golden/generate.py).

Worker ack state is shared topology-wide through one
:class:`~repro.core.transport.WorkerAckRegistry`: every leaf's links to a
given worker encode downlink deltas against the worker's actual acked
base, so a worker re-attached to a surviving leaf after its server died
(``ElasticPool``) keeps its acked-base chain — the new leaf's first
dispatch is a delta, not a raw re-send.

Root failover.  The same registry trick makes the ROOT elastic
(``TopologyConfig.root_failover``, on by default): the root's transport
keeps its per-leaf downlink ack state in a topology-owned
:class:`~repro.core.transport.WorkerAckRegistry`, so when the root dies
(:meth:`Topology.kill_root`) the most senior surviving leaf (attach
order) is promoted in place — its current model becomes the global,
every surviving leaf re-parents its server<->server link to the promoted
root's fresh transport, and because the registry survived, the promoted
root's first dispatch to each leaf is a *delta* against that leaf's
actual acked global — no raw re-sync storm.  In-flight pushes and
fan-outs to the dead root roll back exactly like :meth:`kill_leaf`'s
death path (uplink EF credited back, downlink revert chain unlinked),
and arrived-but-unmerged pushes die with the root's memory — each leaf's
next push re-ships its absolute state as a delta against its still-held
``tx_base``, so no update mass is lost.  Root version, history, and byte
counters continue across the promotion: the root is a *role*, not a
process.
"""
from __future__ import annotations

from dataclasses import dataclass, replace as dc_replace
from typing import Callable, Dict, List, Optional, Sequence

from repro.parallel import sharding as psharding
from repro.runtime import trace

from . import aggregation as agg
from . import flatbuf
from . import population as population_mod
from . import server_opt as server_opt_mod
from . import transport as transport_mod
from .estimator import TimeEstimator
from .events import EventLoop
from .selection import make_pool_selectors
from .server import AggregationServer, HistoryPoint
from .worker import FLWorker


@dataclass
class TopologyConfig:
    """One hierarchical run's shape + server<->server wire parameters."""
    n_leaves: int = 1
    push: str = "sync"            # root merge gate: "sync" barrier | "async"
    push_every: int = 1           # leaf aggregations per upward push
    server_codec: str = "delta"   # leaf->root codec (flat-buffer delta path)
    server_codec_down: Optional[str] = None   # root->leaf (None = symmetric)
    server_frac: float = 0.1
    server_bandwidth: float = 1e9  # bytes/s per server<->server link
    root_aggregator: str = "linear"  # across-leaf weights (staleness, n_data)
    root_alpha: Optional[float] = None  # None: 1.0 sync-push, 0.5 async-push
    root_stale_pow: float = 0.5   # async-push staleness damping exponent
    root_rounds: Optional[int] = None   # cap on global versions
    pools: Optional[Sequence[Sequence[int]]] = None  # worker idx per leaf
    passthrough: bool = False     # 1x1 identity: root colocated, no wire
    root_failover: bool = True    # root death promotes the senior leaf
                                  # (False: root death ends the run)

    def __post_init__(self):
        if self.push not in ("sync", "async"):
            raise ValueError(f"push mode {self.push!r}")
        if self.n_leaves < 1:
            raise ValueError("need at least one leaf")
        if self.push_every < 1:
            raise ValueError("push_every must be >= 1")
        if self.passthrough and self.n_leaves != 1:
            raise ValueError("passthrough is the 1-leaf identity topology")
        agg.check_aggregator(self.root_aggregator, "root aggregator")


def parse_topology(spec, **overrides) -> TopologyConfig:
    """``"1x1"`` / ``"1x4"`` (root x leaves), a leaf count, or a
    :class:`TopologyConfig`.  The 1-leaf string/int spelling is the
    passthrough identity; pass ``TopologyConfig(n_leaves=1,
    passthrough=False)`` explicitly for a 1-leaf topology with a real
    server<->server wire.  ``overrides`` replace config fields."""
    if isinstance(spec, TopologyConfig):
        cfg = spec
    else:
        if isinstance(spec, str):
            parts = spec.lower().split("x")
            if len(parts) == 2:
                if int(parts[0]) != 1:
                    raise ValueError(f"only 1-root topologies: {spec!r}")
                n = int(parts[1])
            elif len(parts) == 1:
                n = int(parts[0])
            else:
                raise ValueError(f"topology spec {spec!r}")
        elif isinstance(spec, int):
            n = spec
        else:
            raise TypeError(f"topology spec {spec!r}")
        cfg = TopologyConfig(n_leaves=n, passthrough=(n == 1))
    if overrides:
        cfg = dc_replace(cfg, **overrides)
    return cfg


class _Leaf:
    """Root-side bookkeeping for one leaf server."""

    __slots__ = ("lid", "server", "link", "bandwidth", "dead", "started",
                 "agg_since_push", "n_data_since_push", "push_inflight",
                 "fan_inflight", "push_rec", "fan_rec", "done_settling",
                 "base_root_version", "merged_base")

    def __init__(self, lid: str, server: AggregationServer, link,
                 bandwidth: float):
        self.lid = lid
        self.server = server
        self.link = link              # root-side server<->server Link
        self.bandwidth = bandwidth
        self.dead = False
        self.started = False
        self.agg_since_push = 0       # leaf aggregates since last push
        self.n_data_since_push = 0    # worker updates folded in since then
        self.push_inflight = None     # leaf->root Payload in flight
        self.fan_inflight = None      # root->leaf Payload in flight
        self.push_rec = None          # checkpoint record of the push leg
        self.fan_rec = None           # checkpoint record of the fan leg
        self.done_settling = None     # pending _leaf_done_settled event
        self.base_root_version = 0    # root version the leaf last installed
        # the exact leaf-model snapshot of this leaf's most recently
        # MERGED push — i.e. the leaf state the current global already
        # contains.  Each fan-out pins it at dispatch and the install
        # re-bases on the pinned copy: anything the leaf merged past
        # that snapshot is NOT in the delivered global and must survive
        self.merged_base = None


class Topology:
    """Root aggregator + orchestrator for one hierarchical run.

    Owns the global model, the server<->server transport (one codec'd
    link per leaf), the fused flat-buffer re-merge, and the root's
    :class:`HistoryPoint` sequence.  It is also every leaf server's
    ``topology_hook``: leaves report aggregates (push trigger) and
    completion through it instead of stopping the loop themselves.
    """

    def __init__(self, *, weights, loop: EventLoop, eval_fn,
                 model_bytes: int, config: TopologyConfig, mesh=None,
                 target_accuracy: Optional[float] = None,
                 server_opt=None):
        self.cfg = config
        self.loop = loop
        self.eval_fn = eval_fn
        self.weights = weights
        self.version = 0
        self.mesh = mesh
        self.model_bytes = model_bytes
        self.target_accuracy = target_accuracy
        self.total_up_bytes = 0
        self.total_down_bytes = 0
        self.leaves: Dict[str, _Leaf] = {}
        self.done = False
        self.failovers = 0
        # (leaf_id, payload codec, had-acked-base) per first post-failover
        # dispatch — the chaos auditor's delta-not-raw-resume evidence
        self.failover_dispatches: List[tuple] = []
        # leaf_id -> (decoded contribution, base root version, n_data,
        # leaf snapshot): pushes that arrived but have not merged yet
        # (the sync barrier)
        self._pending: Dict[str, tuple] = {}
        self._alpha = (config.root_alpha if config.root_alpha is not None
                       else (0.5 if config.push == "async" else 1.0))
        # root-carried server optimizer (core/server_opt.py): leaf merges
        # stay plain FedAvg, the global install takes the optimizer step.
        # Passthrough (1x1) has no root merge — build_topology hands the
        # optimizer to the lone leaf server instead, so 1x1 + server_opt
        # stays bit-identical to the single-server run.
        self.server_opt = server_opt if not config.passthrough else None
        if config.passthrough:
            self.transport = None
            self._server_acks = None
            self._flat = None
        else:
            # the per-leaf downlink ack state lives in a topology-owned
            # registry, NOT inside the transport: it must survive the root
            # transport being rebuilt on failover, so the promoted root's
            # first dispatch to each leaf is a delta against the global
            # the leaf actually holds
            self._server_acks = transport_mod.WorkerAckRegistry()
            self.transport = transport_mod.Transport(
                weights, codec=config.server_codec,
                down_codec=config.server_codec_down,
                frac=config.server_frac, raw_bytes=model_bytes, mesh=mesh,
                ack_registry=self._server_acks)
            self._bind_tuner(self.transport)
            # the same packed merge as the leaf servers, over the bundle
            # the root transport resolved
            self._flat = flatbuf.FlatServerState(weights, mesh=mesh)
            self._flat.server_opt = self.server_opt
        # passthrough: finalize() replaces the root history with the
        # leaf's verbatim, so seeding it with an eval would be dead work
        self.history: List[HistoryPoint] = [] if config.passthrough else [
            HistoryPoint(0.0, 0, self._accuracy(), 0, 0)]

    def _accuracy(self) -> float:
        # as AggregationServer._accuracy: one blocking read per evaluation
        trace.count("host_syncs")
        with trace.span("fl.eval", version=self.version):
            return float(self.eval_fn(self.weights))

    # --- wiring ---
    def _bind_tuner(self, tr) -> None:
        """Bandwidth sources for a ``server_codec="auto"`` backbone: the
        server<->server link rates are *configured* per leaf, so the tuner
        prices them directly — on a fat backbone (~1e9 B/s) the encode
        cost dominates the byte savings and the pricing rule resolves
        raw, while a constrained backbone still compresses.  No-op for
        fixed server codecs (tuner is None)."""
        if tr.tuner is None:
            return

        def _leaf_bw(lid):
            lf = self.leaves.get(lid)
            return None if lf is None else lf.bandwidth

        def _rep_bw():
            if not self.leaves:
                return None
            rates = sorted(lf.bandwidth for lf in self.leaves.values())
            return rates[len(rates) // 2]

        tr.tuner.bind_bandwidth(_leaf_bw, _rep_bw)

    def attach_leaf(self, server: AggregationServer,
                    bandwidth: Optional[float] = None) -> _Leaf:
        lid = server.name
        if lid in self.leaves:
            raise ValueError(f"duplicate leaf {lid!r}")
        link = None if self.cfg.passthrough else self.transport.link(lid)
        lf = _Leaf(lid, server, link,
                   bandwidth if bandwidth is not None
                   else self.cfg.server_bandwidth)
        server.topology_hook = self
        self.leaves[lid] = lf
        return lf

    def start(self):
        if self.cfg.passthrough:
            for lf in self.leaves.values():
                lf.started = True
                lf.server.start()
            return
        # first contact: the root provisions every leaf with the initial
        # global — a real raw dispatch (full model bytes on the wire) that
        # also establishes each link's acked/tx base for the delta codecs
        for lf in self.leaves.values():
            self._fan_out(lf)

    def finalize(self):
        """Post-run bookkeeping: in passthrough the root IS the leaf, so
        the root history becomes the leaf's verbatim (including no-op
        rounds that never aggregate — bit-identity with the single-server
        path is structural, not re-derived)."""
        if self.cfg.passthrough:
            (lf,) = self.leaves.values()
            self.history = [HistoryPoint(p.time, p.version, p.accuracy,
                                         p.n_updates, p.selected,
                                         p.up_bytes, p.down_bytes,
                                         p.retransmits)
                            for p in lf.server.history]
            self.weights = lf.server.weights
            self.version = lf.server.version

    # --- leaf hooks (AggregationServer.topology_hook protocol) ---
    def on_leaf_aggregate(self, server: AggregationServer):
        if self.cfg.passthrough:
            return          # finalize() derives the root view from the leaf
        lf = self.leaves[server.name]
        if lf.dead:
            return
        h = server.history[-1]
        lf.agg_since_push += 1
        lf.n_data_since_push += h.n_updates
        if (lf.agg_since_push >= self.cfg.push_every
                and lf.push_inflight is None):
            self._start_push(lf)

    def on_leaf_done(self, server: AggregationServer):
        if self.cfg.passthrough:
            self.loop.stop()
            return
        lf = self.leaves.get(server.name)
        if lf is None or lf.dead:
            return
        # settle after the current call stack: the final aggregate's
        # on_leaf_aggregate (which may start the final push) runs first
        lf.done_settling = self.loop.call_soon(self._leaf_done_settled, lf)

    def _leaf_done_settled(self, lf: _Leaf):
        lf.done_settling = None
        if self.done or lf.dead:
            return
        if (lf.agg_since_push > 0 and lf.push_inflight is None
                and lf.started):
            self._start_push(lf)       # flush a partial push_every window
        if self.cfg.push == "sync":
            self._maybe_sync_merge()   # barrier no longer waits on this leaf
        self._check_done()

    # --- upward leg: leaf -> root push ---
    def _start_push(self, lf: _Leaf):
        server = lf.server
        server.hold()
        snap = server.weights             # what this push tells the root
        payload = lf.link.encode_up(snap)
        base_rv = lf.base_root_version
        n_data = max(lf.n_data_since_push, 1)
        lf.agg_since_push = 0
        lf.n_data_since_push = 0
        lf.push_inflight = payload
        rec = {"payload": payload, "base_rv": base_rv, "n_data": n_data,
               "snap": snap, "ev": None}
        lf.push_rec = rec
        rec["ev"] = transport_mod.transmit(
            self.loop, lf.link, payload,
            payload.wire_bytes / max(lf.bandwidth, 1.0),
            lambda: self._push_arrive(lf, payload, base_rv, n_data, snap),
            direction="up")

    def resume_push(self, lf: _Leaf, rec: dict, t_abs: float):
        """Re-create a snapshotted in-flight push leg (one schedule)."""
        payload = rec["payload"]
        lf.push_inflight = payload
        lf.push_rec = rec
        base_rv, n_data, snap = rec["base_rv"], rec["n_data"], rec["snap"]
        rec["ev"] = transport_mod.resume_transmit(
            self.loop, lf.link, payload, t_abs,
            lambda: self._push_arrive(lf, payload, base_rv, n_data, snap),
            direction="up")

    def _push_arrive(self, lf: _Leaf, payload, base_rv: int, n_data: int,
                     snap):
        if lf.push_inflight is not payload:
            return        # cancelled (leaf died mid-push); EF already reverted
        lf.push_inflight = None
        lf.push_rec = None
        if self.done:
            lf.link.restore_uplink(payload)
            return
        self.total_up_bytes += payload.wire_bytes   # bytes crossed the wire
        contrib = lf.link.decode_up_vec(payload)
        prev = self._pending.get(lf.lid)
        if prev is not None:
            # a second push landed before the barrier merged the first
            # (async-mode leaves keep aggregating while held): the newer
            # snapshot supersedes the contribution, but it embodies BOTH
            # windows' worker updates — the n_data merge weight must
            # accumulate, or the leaf is under-weighted at the root
            n_data += prev[2]
        self._pending[lf.lid] = (contrib, base_rv, n_data, snap)
        if lf.server.done and lf.agg_since_push > 0 and not lf.dead:
            # the leaf finished while this push was in flight, with more
            # aggregates banked since: flush them now or that final
            # window would never reach the root (done leaves get no
            # fan-out, so nothing re-triggers a push)
            self._start_push(lf)
        if self.cfg.push == "async":
            self._merge()
        else:
            self._maybe_sync_merge()
        self._check_done()

    def _maybe_sync_merge(self):
        if not self._pending:
            return
        # the barrier waits on every leaf that can still contribute this
        # cycle: alive and either not finished, mid-push, or already in
        # the pending set (its final flush)
        expected = {lid for lid, lf in self.leaves.items()
                    if not lf.dead and (not lf.server.done
                                        or lf.push_inflight is not None
                                        or lid in self._pending)}
        if expected.issubset(self._pending.keys()):
            self._merge()

    # --- root merge + downward leg ---
    def _merge(self):
        order = sorted(self._pending)
        entries = [self._pending[lid] for lid in order]
        self._pending.clear()
        for lid, (_, _, _, snap) in zip(order, entries):
            if lid in self.leaves:
                # this global now contains the leaf's snapshot: installs
                # re-base the leaf's in-window progress on it
                self.leaves[lid].merged_base = snap
        ups = [agg.WorkerUpdate(weights=c, staleness=self.version - bv,
                                n_data=nd) for c, bv, nd, _ in entries]
        ws = agg.update_weights(self.cfg.root_aggregator, ups)
        alpha = self._alpha
        if self.cfg.push == "async":
            stale = max(u.staleness for u in ups)
            alpha = self._alpha * (1.0 + stale) ** (-self.cfg.root_stale_pow)
        self.weights = self._flat.merge_rows(
            self.weights, [u.weights for u in ups], ws, alpha)
        self.version += 1
        acc = self._accuracy()
        alive = sum(1 for lf in self.leaves.values() if not lf.dead)
        self.history.append(HistoryPoint(self.loop.now, self.version, acc,
                                         len(ups), alive,
                                         self.total_up_bytes,
                                         self.total_down_bytes,
                                         self.transport.total_retransmits))
        # HistoryPoint feedback for a server_codec="auto" backbone
        self.transport.note_round(self.history[-1])
        if ((self.target_accuracy is not None
             and acc >= self.target_accuracy)
                or (self.cfg.root_rounds is not None
                    and self.version >= self.cfg.root_rounds)):
            self._finish_all()
            return
        if self.cfg.push == "async":
            targets = [self.leaves[lid] for lid in order
                       if lid in self.leaves]
        else:
            targets = list(self.leaves.values())
        for lf in targets:
            if not lf.dead and not lf.server.done and lf.fan_inflight is None:
                self._fan_out(lf)

    def _fan_out(self, lf: _Leaf):
        payload = lf.link.encode_down(self.weights)
        self.total_down_bytes += payload.wire_bytes   # counted at dispatch
        lf.fan_inflight = payload
        # pin the rebase snapshot at dispatch: a newer push may merge (and
        # move lf.merged_base) while this fan is in flight, but THIS
        # global only contains the snapshot merged so far — rebasing the
        # install on the newer one would subtract progress it never held
        v_enc, base = self.version, lf.merged_base
        rec = {"payload": payload, "v_enc": v_enc, "base": base, "ev": None}
        lf.fan_rec = rec
        rec["ev"] = transport_mod.transmit(
            self.loop, lf.link, payload,
            payload.wire_bytes / max(lf.bandwidth, 1.0),
            lambda: self._fan_arrive(lf, payload, v_enc, base),
            direction="down")

    def resume_fan(self, lf: _Leaf, rec: dict, t_abs: float):
        """Re-create a snapshotted in-flight fan-out leg (one schedule)."""
        payload = rec["payload"]
        lf.fan_inflight = payload
        lf.fan_rec = rec
        v_enc, base = rec["v_enc"], rec["base"]
        rec["ev"] = transport_mod.resume_transmit(
            self.loop, lf.link, payload, t_abs,
            lambda: self._fan_arrive(lf, payload, v_enc, base),
            direction="down")

    def resume_done_settled(self, lf: _Leaf, t_abs: float):
        """Re-create a snapshotted pending leaf-done settle (one schedule)."""
        lf.done_settling = self.loop.schedule_abs(
            t_abs, self._leaf_done_settled, lf)

    def _fan_arrive(self, lf: _Leaf, payload, v_enc: int, base=None):
        if lf.fan_inflight is not payload:
            return        # cancelled (leaf died mid-fetch); ack untouched
        lf.fan_inflight = None
        lf.fan_rec = None
        if lf.dead or lf.server.done:
            # never delivered / nothing left to resume: the ack must not
            # advance, the downlink EF revert chain unlinks this encode
            lf.link.restore_downlink(payload)
            self._check_done()
            return
        if self.transport.audit is not None:
            # chaos ledger: this leaf now holds the version-v_enc global
            self.transport.audit.note_fetch(lf.lid, v_enc)
        tree = lf.link.complete_fetch(payload)
        server = lf.server
        if base is not None and server.weights is not base:
            # async leaves keep merging worker responses while held (hold
            # parks only re-dispatch), so the leaf model can be ahead of
            # the snapshot this global merged: that in-window progress
            # must ride onto the new global, not be clobbered by it —
            # install global + (leaf_now - merged_snapshot), the same
            # fused delta-accumulate the async_delta path uses.  When
            # nothing merged past the snapshot (every sync leaf; an idle
            # async one), the identity check keeps the install an exact
            # replace.
            tree = server._flat.apply_delta(tree, server.weights, base)
        server.install_global(tree)
        lf.base_root_version = v_enc
        if not lf.started:
            lf.started = True
            lf.server.start()
        else:
            lf.server.release()
        self._check_done()

    # --- faults / termination ---
    def kill_leaf(self, leaf_id: str):
        """A leaf server dies: its pool goes silent, and every in-flight
        server<->server transfer is rolled back — a push mid-flight never
        reaches (or is counted by) the root and its encoded mass returns
        to the link's uplink EF residual; a fan-out mid-flight never
        advances the root's acked base for this leaf (downlink EF revert
        chain).  The leaf's workers stay alive for re-attachment to a
        surviving leaf (``ElasticPool``)."""
        lf = self.leaves[leaf_id]
        if lf.dead:
            return
        lf.dead = True
        lf.server.done = True
        if lf.push_inflight is not None:
            lf.link.restore_uplink(lf.push_inflight)
            lf.push_inflight = None
            lf.push_rec = None
        if lf.fan_inflight is not None:
            lf.link.restore_downlink(lf.fan_inflight)
            lf.fan_inflight = None
            lf.fan_rec = None
        if self.cfg.push == "sync":
            self._maybe_sync_merge()
        self._check_done()

    def kill_leaf_at(self, t: float, leaf_id: str):
        self.loop.at(t, self.kill_leaf, leaf_id)

    def kill_root(self):
        """The ROOT aggregator dies.  Every in-flight server<->server
        transfer rolls back exactly like :meth:`kill_leaf`'s death path —
        a push mid-flight never reaches (or is counted by) a root, its
        encoded mass returns to the uplink EF residual; a fan-out
        mid-flight never advances the leaf's acked base (downlink revert
        chain).  Pushes that arrived but had not merged died with the
        root's memory — no mass is lost: each leaf's next push re-ships
        its absolute state as a delta against its still-held ``tx_base``.
        With ``root_failover`` the most senior surviving leaf is promoted
        in place (:meth:`_promote_root`); without it the run ends."""
        if self.cfg.passthrough:
            raise ValueError("passthrough topology has no separate root")
        if self.done:
            return
        # the dead process's retransmit timers die with it: in-flight
        # copies may still arrive (and be discarded by the inflight
        # guards below), but nothing re-sends on its behalf
        self.transport.closed = True
        for lf in self.leaves.values():
            if lf.push_inflight is not None:
                lf.link.restore_uplink(lf.push_inflight)
                lf.push_inflight = None
                lf.push_rec = None
            if lf.fan_inflight is not None:
                lf.link.restore_downlink(lf.fan_inflight)
                lf.fan_inflight = None
                lf.fan_rec = None
        self._pending.clear()
        if not self.cfg.root_failover:
            self._finish_all()
            return
        survivors = [lf for lf in self.leaves.values() if not lf.dead]
        if not survivors:
            self._check_done()
            return
        self._promote_root(survivors[0])

    # effectively-infinite: the promoted root is colocated with its leaf,
    # so their transfers cross process memory, not a wire
    _LOOPBACK_BW = 1e18

    def _promote_root(self, promoted: _Leaf):
        """Seniority election (attach order) + re-parenting.  The promoted
        leaf's current model becomes the global — the freshest state the
        new root can serve.  The root transport is rebuilt around it, but
        the per-leaf ack registry (and so every leaf's ``acked_base``
        chain) survives, which is what makes the first post-failover
        dispatch to each survivor a DELTA, not a raw re-sync storm.  Root
        version, history, and byte/retransmit counters carry over: the
        root is a role, and the role continues."""
        self.failovers += 1
        old = self.transport
        self.weights = promoted.server.weights
        if self.server_opt is not None:
            # the optimizer vectors are the ROLE's state (like the ack
            # registry): momentum / second moments ride the promotion;
            # only the packed prev anchor is dropped so the next step
            # re-anchors against the promoted model
            self.server_opt.rebase()
        tr = transport_mod.Transport(
            self.weights, codec=self.cfg.server_codec,
            down_codec=self.cfg.server_codec_down,
            frac=self.cfg.server_frac, raw_bytes=self.model_bytes,
            mesh=self.mesh, ack_registry=self._server_acks)
        # same physical links, same lossy channel, one continuous ledger
        tr.reliability = old.reliability
        tr.rel_estimator = old.rel_estimator
        tr.total_retransmits = old.total_retransmits
        tr.audit = old.audit
        self._bind_tuner(tr)
        if tr.tuner is not None and old.tuner is not None:
            # the feedback schedule (warmup/plateau state) is the ROLE's,
            # not the dead process': carry it across the rebuild
            tr.tuner.__dict__.update(
                {k: v for k, v in old.tuner.__dict__.items()
                 if k not in ("_bw_of", "_rep_bw")})
        # same template structure and mesh: bundle_for hands the rebuilt
        # transport the bundle the root's row buffer already uses
        self.transport = tr
        for lf in self.leaves.values():
            if lf.dead:
                continue
            lf.link = tr.link(lf.lid)
            # the dead root's memory of unmerged in-window progress is
            # gone; the first post-failover install is an exact replace
            lf.merged_base = None
            if lf is promoted:
                lf.bandwidth = self._LOOPBACK_BW
                lf.link.reliability = None    # loopbacks don't drop
        # immediately re-provision every survivor (held leaves mid-push or
        # mid-fetch at the death resume at this fan's arrival; it also
        # re-establishes each link's tx_base before any new push can cut
        # a delta against the new root)
        for lf in self.leaves.values():
            if not lf.dead and not lf.server.done:
                had_base = lf.link.acked_base is not None
                self._fan_out(lf)
                self.failover_dispatches.append(
                    (lf.lid, lf.fan_inflight.codec, had_base))
        self._check_done()

    def kill_root_at(self, t: float):
        self.loop.at(t, self.kill_root)

    def _finish_all(self):
        self.done = True
        for lf in self.leaves.values():
            lf.server.done = True
        self.loop.stop()

    def _check_done(self):
        if self.done:
            return
        if (all(lf.dead or lf.server.done for lf in self.leaves.values())
                and not self._pending
                and not any(lf.push_inflight is not None
                            or lf.fan_inflight is not None
                            for lf in self.leaves.values())):
            self.done = True
            self.loop.stop()


@dataclass
class TopologyResult:
    """One hierarchical run: the root's global history plus per-leaf
    local histories and the orchestrator itself (fault/parity tests
    introspect links and counters through it)."""
    root_history: List[HistoryPoint]
    leaf_histories: Dict[str, List[HistoryPoint]]
    topology: Topology
    config: TopologyConfig


def _partition_pools(n_workers: int, cfg: TopologyConfig) -> List[List[int]]:
    if cfg.pools is not None:
        pools = [list(p) for p in cfg.pools]
        if len(pools) != cfg.n_leaves:
            raise ValueError("one pool per leaf")
        seen = [i for p in pools for i in p]
        if sorted(seen) != list(range(n_workers)):
            raise ValueError("pools must partition the worker set")
        return pools
    return [[i for i in range(n_workers) if i % cfg.n_leaves == j]
            for j in range(cfg.n_leaves)]


def build_topology(setup, *, topology, mode: str = "sync",
                   selector: str = "all", aggregator: str = "fedavg",
                   epochs_per_round: int = 10, max_rounds: int = 60,
                   target_accuracy: Optional[float] = None,
                   selector_kw: Optional[dict] = None,
                   server_freq: float = 3.0, async_alpha: float = 1.0,
                   async_stale_pow: float = 0.0, async_min_updates: int = 1,
                   async_delta: bool = False, async_latest_table: bool = True,
                   transport: str = "raw",
                   transport_down: Optional[str] = None,
                   transport_frac: float = 0.1,
                   server_mesh: Optional[int] = None,
                   cohort: Optional[int] = None, cohort_seed: int = 0,
                   server_opt=None, server_opt_kw: Optional[dict] = None):
    """Construct (but do not run) one hierarchical system: the shared
    event loop, the root :class:`Topology`, and one leaf
    :class:`AggregationServer` per pool with its own estimator, selector,
    transport (sharing a topology-wide :class:`WorkerAckRegistry`) and
    workers.  ``max_rounds`` counts each leaf's LOCAL rounds;
    ``target_accuracy`` is checked on the root's global model (on the
    leaf itself in passthrough, where they are the same model)."""
    cfg = parse_topology(topology)
    loop = EventLoop()
    mesh = None if server_mesh is None else psharding.agg_mesh(server_mesh)
    # leaf merges stay plain FedAvg — the ROOT carries the server
    # optimizer (the global install is the pseudo-gradient step).  In
    # passthrough there is no root merge, so the lone leaf server gets
    # the optimizer instead, keeping 1x1 == single-server bit-exactly.
    opt = server_opt_mod.make_server_opt(server_opt, **(server_opt_kw or {}))
    topo = Topology(weights=setup.weights0, loop=loop, eval_fn=setup.eval_fn,
                    model_bytes=setup.model_bytes, config=cfg, mesh=mesh,
                    target_accuracy=None if cfg.passthrough
                    else target_accuracy,
                    server_opt=None if cfg.passthrough else opt)
    pools = _partition_pools(len(setup.profiles), cfg)
    ack_registry = transport_mod.WorkerAckRegistry()
    transports = [transport_mod.Transport(setup.weights0, codec=transport,
                                          down_codec=transport_down,
                                          frac=transport_frac,
                                          raw_bytes=setup.model_bytes,
                                          mesh=mesh,
                                          ack_registry=ack_registry)
                  for _ in pools]
    ests = [TimeEstimator(server_freq=server_freq,
                          t_onebatch_server=setup.per_batch_server)
            for _ in pools]
    for tr, est, pool in zip(transports, ests, pools):
        if tr.tuner is not None:
            # worker-facing auto: each leaf's tuner prices its OWN
            # estimator's measured link rates (pools are disjoint),
            # seeded by the pool profiles' advertised nominal rates so
            # the first uplink already picks the regime's codec
            nominal = {setup.profiles[i].worker_id:
                       float(setup.profiles[i].bandwidth) for i in pool}
            rep0 = (sorted(nominal.values())[len(nominal) // 2]
                    if nominal else None)

            def _bw_of(wid, _e=est, _n=nominal):
                m = _e.bandwidth(wid)
                return m if m is not None else _n.get(wid)

            def _rep_bw(_e=est, _r=rep0):
                m = _e.median_bandwidth()
                return m if m is not None else _r

            tr.tuner.bind_bandwidth(_bw_of, _rep_bw)
    sels = make_pool_selectors(selector, ests,
                               [t.expected_oneway_bytes for t in transports],
                               **(selector_kw or {}))
    for j, pool in enumerate(pools):
        # one vectorized population per leaf (pools are disjoint, and each
        # leaf's selector prices against its own estimator's lanes);
        # cohorts are drawn per leaf from per-leaf seeded streams
        pop = population_mod.WorkerPopulation()
        ests[j].bind_population(pop)
        server = AggregationServer(
            weights=setup.weights0, loop=loop, estimator=ests[j],
            selector=sels[j], eval_fn=setup.eval_fn,
            model_bytes=setup.model_bytes, aggregator=aggregator, mode=mode,
            epochs_per_round=epochs_per_round, max_rounds=max_rounds,
            target_accuracy=target_accuracy if cfg.passthrough else None,
            async_alpha=async_alpha, async_stale_pow=async_stale_pow,
            async_min_updates=async_min_updates, async_delta=async_delta,
            async_latest_table=async_latest_table, transport=transports[j],
            mesh=mesh, name=f"leaf{j}", population=pop, cohort=cohort,
            cohort_seed=cohort_seed + j,
            server_opt=opt if cfg.passthrough else None)
        for i in pool:
            prof, shard = setup.profiles[i], setup.shards[i]
            server.add_worker(FLWorker(
                prof.worker_id, profile=prof, data=shard,
                train_fn=setup.train_fn, loop=loop,
                per_batch_time=setup.per_batch_server * server_freq /
                max(prof.cpu_freq * prof.cpu_prop, 1e-9)))
        topo.attach_leaf(server)
    return loop, topo


def run_fl_topology(setup, *, topology,
                    on_build: Optional[Callable[[Topology], None]] = None,
                    max_events: int = 200_000,
                    checkpoint_every: Optional[int] = None,
                    checkpoint_dir: Optional[str] = None,
                    checkpoint_keep: int = 3,
                    resume: bool = False,
                    stop_after_checkpoints: Optional[int] = None,
                    **kw) -> TopologyResult:
    """Build and run one hierarchical FL experiment end to end.  ``kw``
    mirrors :func:`repro.core.experiment.run_fl`'s per-server kwargs;
    ``on_build`` runs after construction and before the first dispatch
    (tests install wire spies / fault schedules through it — on a
    ``resume=True`` run it must NOT re-apply past fault schedules: the
    snapshot already carries the injected reliability/audit state).
    ``checkpoint_every``/``checkpoint_dir``/``resume`` snapshot and
    restore the FULL topology state at global-version boundaries (leaf
    version in passthrough, where there is no root counter)."""
    loop, topo = build_topology(setup, topology=topology, **kw)
    if on_build is not None:
        on_build(topo)
    if resume or checkpoint_every is not None:
        from repro.checkpoint import CheckpointManager, FederationSnapshot
        from repro.checkpoint.snapshot import drive_checkpointed
        if checkpoint_dir is None:
            raise ValueError("checkpointing needs checkpoint_dir")
        mgr = CheckpointManager(checkpoint_dir, keep=checkpoint_keep)
        if resume:
            got = mgr.restore_latest()
            if got is None:
                raise FileNotFoundError(
                    f"resume=True but no readable checkpoint in "
                    f"{checkpoint_dir}")
            got[1].restore_topology(loop, topo)
        else:
            topo.start()
        if topo.cfg.passthrough:
            (only,) = topo.leaves.values()
            version_fn = lambda: only.server.version
        else:
            version_fn = lambda: topo.version
        if checkpoint_every is not None:
            drive_checkpointed(
                loop, mgr, version_fn,
                lambda: FederationSnapshot.capture_topology(loop, topo),
                every=checkpoint_every, max_events=max_events,
                stop_after=stop_after_checkpoints)
        else:
            loop.run(max_events=max_events)
    else:
        topo.start()
        loop.run(max_events=max_events)
    if loop.exhausted:
        raise RuntimeError(
            f"event loop exhausted max_events={max_events} with work "
            "still queued — the run did not complete and the histories "
            "would be silently truncated; shrink the run or raise "
            "max_events")
    topo.finalize()
    return TopologyResult(
        root_history=topo.history,
        leaf_histories={lid: lf.server.history
                        for lid, lf in topo.leaves.items()},
        topology=topo, config=topo.cfg)
