"""Aggregation server (thesis §3.1/§3.3): worker registry, selection,
sync/async merge gates, staleness bookkeeping, accuracy-over-time history.

Synchronous mode (thesis §2.1.2.2): responses based on an older server
version than current are *ignored*; a round aggregates when every selected
worker responded (or the straggler timeout fires — our fault-tolerance
extension, which the selection policy then treats as a failure signal).

Asynchronous mode: every arriving response triggers an immediate aggregation
(staleness-weighted, eq 2.4 family) and the responding worker is immediately
re-dispatched — fast workers never wait for slow ones (§2.2.2.4 point 3).
"""
from __future__ import annotations

import random
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from repro.runtime import trace

from . import aggregation as agg
from . import flatbuf
from . import server_opt as server_opt_mod
from . import transport as transport_mod
from .estimator import TimeEstimator, WorkerProfile
from .events import EventLoop
from .population import WorkerPopulation, as_view
from .selection import Selector
from .warehouse import DataWarehouse, Pointer
from .worker import FLWorker, TrainResult


@dataclass
class HistoryPoint:
    time: float
    version: int
    accuracy: float
    n_updates: int
    selected: int
    up_bytes: int = 0        # cumulative worker->server wire bytes so far
    down_bytes: int = 0      # cumulative server->worker wire bytes so far
    retransmits: int = 0     # cumulative lossy-link retransmit count so far
                             # (copies, not bytes — never in up/down_bytes)


class AggregationServer:
    def __init__(self, *, weights, loop: EventLoop, estimator: TimeEstimator,
                 selector: Selector, eval_fn: Callable[[object], float],
                 model_bytes: int, aggregator: str = "fedavg",
                 mode: str = "sync", epochs_per_round: int = 10,
                 max_rounds: int = 100, target_accuracy: Optional[float] = None,
                 straggler_timeout_factor: float = 4.0,
                 async_alpha: float = 1.0, async_stale_pow: float = 0.0,
                 async_min_updates: int = 1, async_delta: bool = False,
                 async_latest_table: bool = True,
                 transport="raw", transport_down: Optional[str] = None,
                 mesh=None, name: str = "aggregator",
                 population: Optional[WorkerPopulation] = None,
                 cohort: Optional[int] = None, cohort_seed: int = 0,
                 max_resident_links: Optional[int] = None,
                 server_opt=None, server_opt_kw: Optional[dict] = None):
        assert mode in ("sync", "async")
        agg.check_aggregator(aggregator)
        self.name = name
        self.address = f"server://{name}"
        self.weights = weights
        # 1-D aggregation-server mesh (parallel.sharding.agg_mesh): the
        # packed merge substrate and every link's flat vectors shard along
        # the parameter axis — None keeps the single-device fused path
        self.mesh = mesh
        self.version = 0
        self.loop = loop
        self.est = estimator
        self.selector = selector
        self.eval_fn = eval_fn
        self.model_bytes = model_bytes
        self.aggregator = aggregator
        self.mode = mode
        self.epochs_per_round = epochs_per_round
        self.max_rounds = max_rounds
        self.target_accuracy = target_accuracy
        self.straggler_timeout_factor = straggler_timeout_factor
        self.async_alpha = async_alpha
        self.async_stale_pow = async_stale_pow
        # the thesis' `synchronous_federate_minimum_client` knob (Listing
        # 4.1 line 3) applied to async: merge once >= this many responses
        # are cached, so eq-2.4 staleness weighting averages across workers
        self.async_min_updates = async_min_updates
        # beyond-paper: merge worker *deltas* (w_new - w_base) into the
        # current server weights instead of alpha-mixing absolute weights
        # (FedBuff-style); staleness costs far less because concurrent
        # updates compose additively.
        self.async_delta = async_delta
        # eq 2.2/2.4 faithful mode: aggregate over each worker's *latest*
        # response; False = FedAsync-style single-arrival alpha-nudging
        self.async_latest_table = async_latest_table
        self._dispatch_base: Dict[str, object] = {}
        self._latest: Dict[str, tuple] = {}   # async: worker -> latest response
        # the flat-buffer merge: persistent (W, N) update rows that decoded
        # payloads land in, one fused pass per merge (rejects weight trees
        # that do not pack)
        self._flat = flatbuf.FlatServerState(weights, mesh=mesh)
        # optional server-side optimizer (core/server_opt.py): rides the
        # merge tails as one fused pass
        self.server_opt = server_opt_mod.make_server_opt(
            server_opt, **(server_opt_kw or {}))
        self._flat.server_opt = self.server_opt
        # single weight-exchange path: every transfer is a codec'd Payload
        # with exact wire bytes (core/transport.py); transport_down names
        # the downlink codec (None = symmetric with the uplink)
        if isinstance(transport, str):
            transport = transport_mod.Transport(weights, codec=transport,
                                                down_codec=transport_down,
                                                raw_bytes=model_bytes,
                                                mesh=mesh)
        if transport.bundle is not self._flat.bundle:
            # decoded vectors must match the row buffer's padded width
            raise ValueError("transport and server resolve different "
                             "bundles (weight structure or mesh differ)")
        self.transport = transport
        self.total_up_bytes = 0
        self.total_down_bytes = 0
        # --- massive-scale control plane (core/population.py) ---
        # population: vectorized per-worker lanes — selection prices eq 3.4
        # population-wide in one fused pass instead of a per-object scan.
        # cohort: sample this many alive workers per round; only cohort
        # members get links/tickets/events, so per-round cost scales with
        # the cohort, not W.  Under a cohort the (W, N) row buffer shrinks
        # to a claimed-row window (O(cohort x N) peak) and resident link
        # state is LRU-bounded by max_resident_links.
        self.population = population
        self.cohort = cohort
        self._cohort_rng = (random.Random(cohort_seed)
                            if cohort is not None else None)
        if max_resident_links is None and cohort is not None:
            max_resident_links = max(4 * cohort, 64)
        self.max_resident_links = max_resident_links
        self._profiles_view = None          # cached population view
        self._row_of: Dict[str, int] = {}   # worker -> claimed window row
        self._window = cohort is not None
        if self._window:
            self._flat.window_rows = cohort
        self._inflight_w: set = set()       # dispatched, response pending

        # hierarchical topology (core/topology.py): when set, this server is
        # a LEAF under a root aggregator — _finish defers the loop-stop
        # decision to the orchestrator, every aggregate is reported upward
        # (the leaf-push hook), and hold()/release() gate dispatch while a
        # pushed model's global replacement is in flight
        self.topology_hook = None
        self._hold = False
        self._held: List[str] = []          # async workers parked while held
        self._pending_dispatch = False      # sync round deferred while held
        self._started = False               # start() called (mid-run joins)

        self.workers: Dict[str, FLWorker] = {}
        self.warehouse = DataWarehouse()
        self.pointer = Pointer(self.address, self.warehouse.put(weights))
        self._cache: List[agg.WorkerUpdate] = []
        self._outstanding: set = set()
        self._round_open = False
        self._round_id = 0
        # pending-timer handles (checkpoint bookkeeping): the live event
        # for the current round's straggler timeout / the no-op-round
        # re-dispatch, so a snapshot can serialize and re-create them
        self._timeout_ev = None
        self._timeout_rid = 0
        self._noop_ev = None
        self.history: List[HistoryPoint] = [
            HistoryPoint(0.0, 0, self._accuracy(), 0, 0)]
        self.done = False

    # --- relationship (thesis §3.3.1) ---
    def add_worker(self, worker: FLWorker):
        joined_mid_run = (self._started and self.mode == "async"
                          and worker.worker_id not in self.workers
                          and not self.done)
        self.workers[worker.worker_id] = worker
        if self.population is not None:
            self.population.adopt(worker.profile)
        self._profiles_view = None
        worker.add_server(self.pointer)
        if joined_mid_run:
            # async servers dispatch per-response, so a worker joining a
            # RUNNING async server (elastic join / topology re-attach) has
            # no response of its own to trigger on — kick its first
            # instruction now (sync servers pick it up at the next
            # round's selection instead)
            if self._hold:
                self._held.append(worker.worker_id)
            else:
                self._send_train(worker.worker_id, self.version)

    def remove_worker(self, worker_id: str):
        w = self.workers.pop(worker_id, None)
        if self.population is not None:
            self.population.release(worker_id)
        self._profiles_view = None
        # NOTE: _latest / _row_of entries survive removal on purpose — the
        # async latest-table keeps a departed worker's last response in the
        # merge (legacy behaviour), so its claimed window row must stay
        # claimed until the mode's normal release point
        if w is not None:
            # a departing worker's in-flight transfers are cancelled and
            # its ACL entry revoked: once the server forgets the worker,
            # a late response could never be redeemed (_on_response can't
            # reach the departed worker's warehouse), so letting it
            # deliver would leak the one-time ticket plus a model-sized
            # payload forever — and a still-training instruction must not
            # issue a ticket to a server that will never redeem it
            w.cancel_inflight(self.pointer)
            w.remove_server(self.pointer)

    def profiles(self):
        """Registered workers' profiles, in registry order — a
        ``PopulationView`` (lane vectors + profile sequence) when a
        population is bound, the legacy list otherwise."""
        if self.population is not None:
            if self._profiles_view is None:
                self._profiles_view = self.population.view_for(self.workers)
            return self._profiles_view
        return [w.profile for w in self.workers.values()]

    # --- main loop ---
    def start(self):
        self._started = True
        self._dispatch_round()

    def _accuracy(self) -> float:
        # the evaluation contract returns a host float: a blocking read
        trace.count("host_syncs")
        with trace.span("fl.eval", version=self.version):
            return float(self.eval_fn(self.weights))

    def _finish(self):
        self.done = True
        if self.topology_hook is not None:
            self.topology_hook.on_leaf_done(self)
        else:
            self.loop.stop()

    # --- leaf role under a root aggregator (core/topology.py) ---
    def hold(self):
        """Topology gate: freeze new dispatches — a leaf push is in flight
        and the root's global replacement hasn't been installed yet."""
        self._hold = True

    def release(self):
        """Re-open dispatch after :meth:`install_global`: re-run a sync
        round deferred while held, re-dispatch async workers parked in
        ``_held``."""
        if not self._hold:
            return
        self._hold = False
        if self.done:
            self._held.clear()
            return
        held, self._held = self._held, []
        for wid in held:
            if wid in self.workers:
                self._send_train(wid, self.version)
        if self._pending_dispatch:
            self._pending_dispatch = False
            self._dispatch_round()

    def install_global(self, weights) -> None:
        """Replace this (leaf) server's model with the root's new global —
        the downward leg of the hierarchy.  The pointer uid is stable so
        workers' ACLs keep working; the leaf version is NOT bumped
        (staleness is counted in leaf rounds, and sync's stale-discard
        must not fire on an install that landed between rounds)."""
        self.weights = weights
        self.warehouse.put(weights, uid=self.pointer.uid)

    def _dispatch_round(self):
        with trace.span("fl.dispatch", version=self.version):
            if self.done:
                return
            if self._hold:
                # held by the topology layer: remember that a round wants to
                # open; release() re-enters once the new global is installed
                self._pending_dispatch = True
                return
            if self.version >= self.max_rounds:
                self._finish()
                return
            pool = self.profiles()
            with trace.span("fl.select", pool=len(pool)):
                if self.cohort is not None:
                    pool = self._sample_cohort(pool)
                selected = self.selector.select(pool)
            self._round_id += 1
            if not selected:
                # nothing admitted (e.g. Alg2 with T=0): burn a no-op round
                # so the policy's on_round_end can open the time budget
                # (eq 3.3)
                acc = self.history[-1].accuracy
                self.selector.on_round_end(acc)
                self.history.append(HistoryPoint(
                    self.loop.now, self.version, acc, 0, 0,
                    self.total_up_bytes, self.total_down_bytes,
                    self.transport.total_retransmits))
                self.transport.note_round(self.history[-1])
                self.version += 1
                self._noop_ev = self.loop.schedule(1e-3, self._noop_dispatch)
                return
            self._outstanding = set(selected)
            self._round_open = True
            base_version = self.version
            rid = self._round_id
            down_b = {wid: self._send_train(wid, base_version)
                      for wid in selected}
            if self.mode == "sync":
                # straggler timeout: aggregate with whatever arrived; the round
                # trip costs the *actual* encoded dispatch down (first-contact
                # dispatches ship the full raw model even under a compressed
                # downlink codec) plus the codec'd response up
                up_b = self.transport.expected_up_bytes()
                t_max = max(self.est.t_one(self.workers[w].profile) *
                            self.epochs_per_round +
                            self.est.t_transmit(self.workers[w].profile,
                                                down_b[w]) +
                            self.est.t_transmit(self.workers[w].profile, up_b)
                            for w in selected)
                self._timeout_rid = rid
                self._timeout_ev = self.loop.schedule(
                    self.straggler_timeout_factor * max(t_max, 1e-3),
                    self._round_timeout, rid)

    def _sample_cohort(self, pool):
        """Seeded per-round cohort draw: sample ``cohort`` of the ALIVE
        workers (dead lanes never enter the draw, so a chaos kill of a
        never-contacted worker costs nothing) and return the pool filtered
        to the draw, order preserved.  At ``cohort >= alive`` the draw is
        the whole alive pool, so selection — and therefore the run — is
        bit-identical to no cohort at all."""
        view = as_view(pool)
        if view is not None:
            alive = view.ids_where(view.alive_mask())
        else:
            alive = [p.worker_id for p in pool if not p.failed]
        chosen = set(self._cohort_rng.sample(alive,
                                             min(self.cohort, len(alive))))
        if view is not None:
            mask = np.fromiter((wid in chosen for wid in view.worker_ids()),
                               bool, len(view))
            return view.where(mask)
        return [p for p in pool if p.worker_id in chosen]

    def _send_train(self, wid: str, base_version: int) -> int:
        """Dispatch one train instruction; returns the actual downlink
        payload bytes (what the straggler timeout must be priced on)."""
        w = self.workers.get(wid)
        if w is None:
            return 0
        link = self.transport.link(wid)
        down = link.encode_down(self.weights)
        self.total_down_bytes += down.wire_bytes
        if self.async_delta:
            # a compressed downlink's worker starts from the (lossy)
            # reconstruction: the delta-accumulate then reads the packed
            # link.tx_base instead of this base
            self._dispatch_base[wid] = self.weights
        self._inflight_w.add(wid)
        w.train_async(self.pointer, down, base_version,
                      self.epochs_per_round, link, self._on_response)
        return down.wire_bytes

    # --- response handling (thesis §3.3.3 steps 8-9) ---
    def _on_response(self, res: TrainResult):
        with trace.span("fl.respond", worker=res.worker_id,
                        version=res.base_version):
            w = self.workers.get(res.worker_id)
            if w is None:
                return
            # redeem FIRST (and unconditionally): redemption deletes the stored
            # payload, so stale/late responses can't leak a model-sized buffer
            # plus a live ticket in the worker's warehouse forever
            payload = w.warehouse.redeem_ticket(res.weights_ticket)
            self._inflight_w.discard(res.worker_id)
            if self.done:
                return
            self.total_up_bytes += res.up_bytes   # the bytes crossed the wire
            self.est.observe_training(res.worker_id,
                                      res.t_train / max(res.epochs, 1))
            self.est.observe_transmit(res.worker_id, res.t_up, res.up_bytes)
            staleness = self.version - res.base_version
            if self.population is not None:
                self.population.note_response(res.worker_id, res.base_version,
                                              staleness)
            if self.mode == "sync" and staleness > 0:
                # thesis: sync ignores results that straddle an aggregation —
                # but the encoded mass must go back into the link's EF residual
                # or it is silently lost from the error-feedback contract
                self.transport.link(res.worker_id).restore_uplink(payload)
                return
            link = self.transport.link(res.worker_id)
            with trace.span("fl.decode_up", worker=res.worker_id):
                # decode straight to a packed flat vector (for compressed
                # codecs: base + dequantised delta in one fused pass); it
                # lands in the (W, N) row buffer at merge time
                weights = link.decode_up_vec(payload)
            if self.async_delta and self.mode == "async":
                # delta-accumulate in flat-vector space: cur + (new - base);
                # delta codecs already hold the packed base on the link
                base = self._dispatch_base.get(res.worker_id, self.weights)
                base_vec = (link.tx_base if self.transport.tracks_tx_base
                            else self._flat.bundle.pack(base))
                weights = self._flat.delta_vec(self.weights, weights,
                                               base_vec)
            if self._window:
                # streaming cohort-windowed merge: the decoded vector lands in
                # a claimed window row NOW, and from here on this update is
                # identified by its row INDEX — `_cache`/`_latest` carry the
                # int through the existing rebuild logic untouched, and the
                # merge contracts the window with weights scattered by row.
                # A re-responding worker (async latest-table) overwrites its
                # own stable row.
                row = self._row_of.get(res.worker_id)
                if row is None:
                    row = self._flat.win_claim()
                    self._row_of[res.worker_id] = row
                self._flat.win_write(row, weights)
                weights = row
            self._outstanding.discard(res.worker_id)
            if self.mode == "async":
                if self.async_latest_table:
                    # eq 2.2/2.4: the async aggregate averages *each worker's
                    # latest response* (whatever server version it was based
                    # on), staleness-weighted at merge time.
                    self._latest[res.worker_id] = (weights, res.base_version,
                                                   max(res.n_batches, 1))
                    self._cache = [
                        agg.WorkerUpdate(weights=wt,
                                         staleness=self.version - bv,
                                         n_data=nd)
                        for (wt, bv, nd) in self._latest.values()]
                else:
                    self._cache.append(agg.WorkerUpdate(
                        weights=weights, staleness=staleness,
                        n_data=max(res.n_batches, 1)))
                if len(self._cache) >= self.async_min_updates:
                    self._aggregate()
                else:
                    self._cache = []
                    if self._window and not self.async_latest_table:
                        # discarded below-min updates: recycle their rows
                        for row in self._row_of.values():
                            self._flat.win_release(row)
                        self._row_of.clear()
                if not self.done:
                    if self._hold:
                        self._held.append(res.worker_id)
                    else:
                        self._send_train(res.worker_id, self.version)
            else:
                self._cache.append(agg.WorkerUpdate(
                    weights=weights, staleness=staleness,
                    n_data=max(res.n_batches, 1)))
                if not self._outstanding:
                    self._aggregate()
                    if not self.done:
                        self._dispatch_round()

    def _noop_dispatch(self):
        """The deferred re-dispatch of an empty-selection round (tracked so
        a snapshot can serialize the pending timer)."""
        self._noop_ev = None
        self._dispatch_round()

    def resume_noop_dispatch(self, t_abs: float):
        """Re-create a snapshotted no-op-round re-dispatch timer.  Consumes
        exactly one ``loop.schedule`` call (see
        :meth:`FLWorker.resume_conversation`)."""
        self._noop_ev = self.loop.schedule_abs(t_abs, self._noop_dispatch)

    def resume_round_timeout(self, rid: int, t_abs: float):
        """Re-create a snapshotted straggler-timeout timer (one schedule)."""
        self._timeout_rid = rid
        self._timeout_ev = self.loop.schedule_abs(t_abs,
                                                  self._round_timeout, rid)

    def _round_timeout(self, rid: int):
        if rid == self._timeout_rid:
            self._timeout_ev = None
        if self.done or rid != self._round_id or not self._round_open:
            return
        if self.mode == "sync" and self._outstanding:
            # mark non-responders failed so selection stops picking them,
            # and cancel exactly OUR in-flight transfer from each (round
            # closed: the unredeemed ticket is dead weight, and the link's
            # EF residual gets the undelivered mass back) — scoped per
            # dispatch so other servers' tickets in the same warehouse
            # are untouched
            for wid in list(self._outstanding):
                if wid in self.workers:
                    self.workers[wid].profile.failed = True
                    self.workers[wid].cancel_inflight(self.pointer)
                self._inflight_w.discard(wid)
            self._outstanding.clear()
            if self._cache:
                self._aggregate()
            if not self.done:
                self._dispatch_round()

    def _aggregate(self):
        if not self._cache:
            return
        self._round_open = False
        with trace.span("fl.merge", version=self.version + 1,
                        rows=len(self._cache)):
            # async merges are damped (FedAsync-style server mixing): a
            # single worker's response nudges the global model instead of
            # replacing it, scaled down further for stale responses (eq 2.4
            # family).
            if self.mode == "async" and not self.async_latest_table:
                stale = max(u.staleness for u in self._cache)
                alpha = (self.async_alpha
                         * (1.0 + stale) ** (-self.async_stale_pow))
            else:
                alpha = 1.0
            ws = agg.update_weights(self.aggregator, self._cache)
            if self._window:
                # cohort window: cache entries carry claimed row indices; the
                # merge contracts the O(cohort x N) window with each weight
                # scattered to its row (same fused kernel as merge_rows)
                self.weights = self._flat.merge_window(
                    self.weights, [u.weights for u in self._cache], ws, alpha)
                if not (self.mode == "async" and self.async_latest_table):
                    # sync / single-arrival async: merged rows are dead —
                    # recycle them (latest-table workers keep stable rows,
                    # matching the legacy table's keep-latest semantics)
                    for row in self._row_of.values():
                        self._flat.win_release(row)
                    self._row_of.clear()
            else:
                # responses were decoded straight to packed flat vectors;
                # land them in the (W, N) row buffer and fuse the
                # staleness-weighted sum + alpha-mix in one pass
                self.weights = self._flat.merge_rows(
                    self.weights, [u.weights for u in self._cache], ws, alpha)
            # the pointer names the *model*: overwrite in place, uid stays
            # stable (workers' ACLs hold this pointer — thesis §3.3.1 step 7)
            self.warehouse.put(self.weights, uid=self.pointer.uid)
        n_upd = len(self._cache)
        self._cache = []
        if self.max_resident_links is not None:
            # bound resident link state to O(active cohorts): evict the
            # coldest quiescent links — never one mid-conversation (in-
            # flight response, claimed window row, parked while held)
            keep = (self._outstanding | self._inflight_w
                    | set(self._row_of) | set(self._held))
            self.transport.lru_evict(keep, self.max_resident_links)
        self.version += 1
        acc = self._accuracy()
        self.selector.on_round_end(acc)
        self.history.append(HistoryPoint(self.loop.now, self.version, acc,
                                         n_upd, n_upd, self.total_up_bytes,
                                         self.total_down_bytes,
                                         self.transport.total_retransmits))
        # HistoryPoint feedback to the auto codec tuner (no-op when a
        # fixed codec is configured — tuner is None)
        self.transport.note_round(self.history[-1])
        if self.target_accuracy is not None and acc >= self.target_accuracy:
            self._finish()
        elif self.version >= self.max_rounds:
            self._finish()
        if self.topology_hook is not None:
            # leaf-push hook LAST: the orchestrator sees the appended
            # history point (and, on the final round, the done flag)
            self.topology_hook.on_leaf_aggregate(self)


def run_sequential(*, weights, train_fn, eval_fn, data, per_batch_time: float,
                   n_batches: int, epochs_per_round: int = 10,
                   max_rounds: int = 100,
                   target_accuracy: Optional[float] = None) -> List[HistoryPoint]:
    """The thesis' sequential baseline: all data in one place, trained
    single-threaded; simulated time = per-batch time x batches x epochs."""
    history = [HistoryPoint(0.0, 0, float(eval_fn(weights)), 0, 0)]
    t = 0.0
    for r in range(max_rounds):
        weights = train_fn(weights, data["x"], data["y"], epochs_per_round)
        t += per_batch_time * n_batches * epochs_per_round
        acc = float(eval_fn(weights))
        history.append(HistoryPoint(t, r + 1, acc, 1, 1))
        if target_accuracy is not None and acc >= target_accuracy:
            break
    return history
