"""FL worker (thesis §3.1.5/§3.3): holds a local model + data shard, obeys
train instructions from its aggregation server, responds with weights via
the warehouse's one-time-ticket channel.

Numerics run for real (jitted JAX); durations are simulated from the same
profile statistics the estimator sees — but with the *true* per-worker
speed, so estimation error (eq 3.4 vs reality) is part of the simulation,
exactly as in the thesis where estimates are refined by measurement.

Every in-flight train conversation keeps a phase record in ``_conv``
(fetch → train → send), holding exactly the inputs the pending event will
consume when it fires.  A checkpoint reads those records to serialize the
leg; :meth:`FLWorker.resume_conversation` re-creates the pending event
from one, bit-identically.  The records are pure bookkeeping — no
behavior of the live run reads them.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax

from repro.runtime import trace

from .estimator import WorkerProfile
from .events import EventLoop
from .transport import Link, Payload, resume_transmit, transmit
from .warehouse import DataWarehouse, Pointer


@dataclass
class TrainResult:
    worker_id: str
    weights_ticket: str
    base_version: int         # server version the worker trained from
    epochs: int
    n_batches: int
    t_train: float            # measured training time (simulated clock)
    t_up: float = 0.0         # measured uplink transmit time
    up_bytes: int = 0         # exact wire bytes of the encoded response


class FLWorker:
    # slotted: a massive-scale population instantiates one of these per
    # worker up front, and the fixed layout roughly halves the per-object
    # footprint (measured in benchmarks/scale_bench.py)
    __slots__ = ("worker_id", "address", "profile", "data", "train_fn",
                 "loop", "warehouse", "server_pointers", "_inflight",
                 "_fetching", "_conv", "busy", "_per_batch_time",
                 "_shard")

    def __init__(self, worker_id: str, *, profile: WorkerProfile,
                 data: Dict, train_fn: Callable, loop: EventLoop,
                 per_batch_time: Optional[float] = None):
        self.worker_id = worker_id
        self.address = f"worker://{worker_id}"
        self.profile = profile
        self.data = data
        # device copy of data's (x, y), made at the first training: the
        # shard never changes, so every later training reuses it
        self._shard = None
        self.train_fn = train_fn       # (params, x, y, epochs) -> params
        self.loop = loop
        self.warehouse = DataWarehouse()
        self.server_pointers: List[Pointer] = []   # ACL (thesis §3.3.3 step 4)
        # in-flight uplink per server: (ticket, payload, link) from ticket
        # issue until delivery — lets a server cancel exactly its own
        # transfer (round closed) without touching other servers' tickets
        self._inflight: Dict[Pointer, tuple] = {}
        # in-flight downlink fetch per server: (payload, link) from dispatch
        # until the fetch-complete event — a round close mid-fetch cancels
        # it, and the link's ack/downlink-EF state must not advance
        self._fetching: Dict[Pointer, tuple] = {}
        # per-server conversation phase record (checkpoint bookkeeping)
        self._conv: Dict[Pointer, dict] = {}
        self.busy = False
        # ground-truth speed (may differ from the estimator's eq-3.4 guess)
        self._per_batch_time = per_batch_time if per_batch_time is not None \
            else 0.05 * 3.0 / max(profile.cpu_freq * profile.cpu_prop, 1e-9)

    # --- relationship API (thesis §3.3.1) ---
    def add_server(self, server_pointer: Pointer):
        self.server_pointers.append(server_pointer)

    def accepts(self, server_pointer: Pointer) -> bool:
        return server_pointer in self.server_pointers

    def remove_server(self, server_pointer: Pointer):
        """Revoke a server's ACL entry (the server dropped this worker):
        in-progress instructions from it die silently at their next
        ``accepts`` check instead of responding to a registry that no
        longer knows the worker."""
        if server_pointer in self.server_pointers:
            self.server_pointers.remove(server_pointer)

    def cancel_inflight(self, server_pointer: Pointer) -> None:
        """Cancel this server's in-flight transfers (its round closed).
        An unfinished *fetch* is dropped without advancing the downlink
        ack (the next dispatch's delta re-carries its mass, so the down
        EF residual reverts); an in-transit *uplink* has its one-time
        credential revoked, the stored payload deleted, and the encoded
        mass credited back into the link's error-feedback residual."""
        fetch = self._fetching.pop(server_pointer, None)
        if fetch is not None:
            down, link = fetch
            rec = self._conv.get(server_pointer)
            if rec is not None and rec.get("down") is down:
                self._conv.pop(server_pointer)
            link.restore_downlink(down)
            self.busy = False
        entry = self._inflight.pop(server_pointer, None)
        if entry is not None:
            ticket, up, link = entry
            rec = self._conv.get(server_pointer)
            if rec is not None and rec.get("ticket") == ticket:
                self._conv.pop(server_pointer)
            self.warehouse.revoke_ticket(ticket)
            link.restore_uplink(up)

    def true_t_one(self) -> float:
        return self._per_batch_time * max(self.profile.n_batches, 0)

    def true_t_transmit(self, model_bytes: int) -> float:
        return model_bytes / max(self.profile.bandwidth, 1.0)

    # --- training API (thesis §3.3.3) ---
    def train_async(self, server_pointer: Pointer, down: Payload,
                    base_version: int, epochs: int, link: Link,
                    on_done: Callable[[TrainResult], None]):
        """Simulates one train instruction end to end: fetch the server
        weights (T_transmit over the actual downlink payload bytes), train
        (T_one * r), encode the response through the link's codec, and
        respond (T_transmit over the actual encoded uplink payload bytes).
        ``on_done`` fires on the event loop at the right time.

        Stateful (delta) downlink codecs schedule an explicit
        fetch-complete event: the worker decodes against its last-acked
        base and advances the ack exactly then, so a fetch that is
        cancelled (round closed) or dies mid-flight never advances the
        link state.  For codecs whose uplink size is known before training
        (raw, delta, int8) the rest of the chain is one scheduled event;
        top-k codecs must train first to know how many coordinates survive
        the threshold, so they schedule the respond leg separately after
        encoding."""
        if not self.accepts(server_pointer) or self.profile.failed:
            # a dispatch that never lands: un-debit the downlink EF state
            link.restore_downlink(down)
            return  # silently drop: a failed/foreign request never responds
        self.busy = True
        t_fetch = self.true_t_transmit(down.wire_bytes)
        if link.needs_down_ack or link.reliability is not None:
            # stateful downlink: decode + ack at the fetch-complete event.
            # A lossy link routes even stateless payloads through here —
            # the channel must deliver before the worker can decode, and
            # the staged event is what transmit() retransmits against.
            self._fetching[server_pointer] = (down, link)
            rec = {"phase": "fetch", "down": down,
                   "base_version": base_version, "epochs": epochs,
                   "ev": None}
            self._conv[server_pointer] = rec
            rec["ev"] = transmit(
                self.loop, link, down, t_fetch,
                lambda: self._fetch_done(server_pointer, down,
                                         base_version, epochs, link,
                                         on_done),
                direction="down")
            return
        with trace.span("fl.decode_down", worker=self.worker_id):
            weights = link.decode_down(down)
        self._after_fetch(server_pointer, weights, base_version, epochs,
                          link, on_done, t_fetch)

    def _fetch_done(self, server_pointer: Pointer, down: Payload,
                    base_version: int, epochs: int, link: Link, on_done):
        entry = self._fetching.get(server_pointer)
        if entry is None or entry[0] is not down:
            # this fetch was cancelled (round closed; ack untouched, down
            # EF reverted). A newer dispatch may already own the slot.
            return
        self._fetching.pop(server_pointer)
        rec = self._conv.get(server_pointer)
        if rec is not None and rec.get("down") is down:
            self._conv.pop(server_pointer)
        if self.profile.failed:          # died mid-fetch: never received
            link.restore_downlink(down)
            self.busy = False
            return
        # the explicit fetch-complete event: decode against the local
        # acked base and advance the ack — even if this worker now dies
        # mid-round, the server knows which base it holds.  Stateless
        # downlinks staged here only for the lossy channel skip the ack
        # bookkeeping entirely
        with trace.span("fl.decode_down", worker=self.worker_id):
            if link.needs_down_ack:
                weights = link.complete_fetch(down)
            else:
                weights = link.decode_down(down)
        self._after_fetch(server_pointer, weights, base_version, epochs,
                          link, on_done, 0.0)

    def _train(self, weights, epochs: int, base_version: int):
        with trace.span("fl.train", worker=self.worker_id,
                        version=base_version):
            if not len(self.data["x"]):
                return weights      # no local data: echo (setup-3 zeros)
            if self._shard is None:
                trace.count("shard_uploads")
                self._shard = (trace.to_device(self.data["x"]),
                               trace.to_device(self.data["y"]))
            else:
                trace.count("shard_hits")
            return self.train_fn(weights, *self._shard, epochs)

    def _after_fetch(self, server_pointer: Pointer, weights,
                     base_version: int, epochs: int, link: Link, on_done,
                     t_fetch: float):
        """Train + respond, scheduled ``t_fetch`` from now (0.0 when called
        from the fetch-complete event itself)."""
        if link.t.audit is not None:
            # chaos ledger: this worker now holds the model of this server
            # version — the monotone-version invariant's raw material
            link.t.audit.note_fetch(self.worker_id, base_version)
        t_train = self.true_t_one() * epochs
        up_bytes = link.upfront_up_bytes()
        if up_bytes is not None and link.reliability is None:
            # single-event fast path: only on a perfect wire — a lossy
            # uplink must go through the staged _inflight protocol so the
            # channel has a cancellable in-flight record to retransmit
            rec = {"phase": "train_fast", "weights": weights,
                   "base_version": base_version, "epochs": epochs,
                   "up_bytes": up_bytes, "t_train": t_train, "ev": None}
            self._conv[server_pointer] = rec
            self._schedule_finish(server_pointer, link, on_done, rec,
                                  t_fetch + t_train +
                                  self.true_t_transmit(up_bytes))
            return
        rec = {"phase": "train", "weights": weights,
               "base_version": base_version, "epochs": epochs,
               "t_train": t_train, "ev": None}
        self._conv[server_pointer] = rec
        self._schedule_train_send(server_pointer, link, on_done, rec,
                                  t_fetch + t_train)

    def _schedule_finish(self, server_pointer: Pointer, link: Link,
                         on_done, rec: dict, delay: float, *,
                         at_abs: Optional[float] = None):
        weights, epochs = rec["weights"], rec["epochs"]
        base_version, t_train = rec["base_version"], rec["t_train"]
        up_bytes = rec["up_bytes"]

        def _finish():
            # the event has fired: drop rec -> event -> _finish -> rec, a
            # cycle that would keep this training's input model alive
            # until the cyclic garbage collector runs
            rec["ev"] = None
            if self._conv.get(server_pointer) is rec:
                self._conv.pop(server_pointer)
            # died mid-training, or the server dropped this worker
            # (remove_server): a response would never be redeemed
            if self.profile.failed or not self.accepts(server_pointer):
                self.busy = False
                return
            up = link.encode_up(self._train(weights, epochs, base_version))
            assert up.wire_bytes == up_bytes, (up.wire_bytes, up_bytes)
            ticket = self.warehouse.issue_ticket(self.warehouse.put(up))
            self.busy = False
            on_done(TrainResult(self.worker_id, ticket, base_version,
                                epochs, self.profile.n_batches, t_train,
                                t_up=self.true_t_transmit(up.wire_bytes),
                                up_bytes=up.wire_bytes))
        rec["ev"] = (self.loop.schedule_abs(at_abs, _finish)
                     if at_abs is not None
                     else self.loop.schedule(delay, _finish))

    def _schedule_train_send(self, server_pointer: Pointer, link: Link,
                             on_done, rec: dict, delay: float, *,
                             at_abs: Optional[float] = None):
        weights, epochs = rec["weights"], rec["epochs"]
        base_version, t_train = rec["base_version"], rec["t_train"]

        def _train_then_send():
            rec["ev"] = None             # fired: break the cycle (_finish)
            if self._conv.get(server_pointer) is rec:
                self._conv.pop(server_pointer)
            # died mid-training, or the server dropped this worker
            if self.profile.failed or not self.accepts(server_pointer):
                self.busy = False
                return
            up = link.encode_up(self._train(weights, epochs, base_version))
            ticket = self.warehouse.issue_ticket(self.warehouse.put(up))
            self._inflight[server_pointer] = (ticket, up, link)
            t_up = self.true_t_transmit(up.wire_bytes)
            srec = {"phase": "send", "ticket": ticket, "up": up,
                    "base_version": base_version, "epochs": epochs,
                    "t_train": t_train, "t_up": t_up, "ev": None}
            self._conv[server_pointer] = srec
            self._schedule_send(server_pointer, link, on_done, srec, t_up)
        rec["ev"] = (self.loop.schedule_abs(at_abs, _train_then_send)
                     if at_abs is not None
                     else self.loop.schedule(delay, _train_then_send))

    def _schedule_send(self, server_pointer: Pointer, link: Link, on_done,
                       rec: dict, delay: float, *, resumed: bool = False,
                       at_abs: Optional[float] = None):
        ticket, up = rec["ticket"], rec["up"]
        base_version, epochs = rec["base_version"], rec["epochs"]
        t_train, t_up = rec["t_train"], rec["t_up"]

        def _send():
            entry = self._inflight.get(server_pointer)
            if entry is None or entry[0] != ticket:
                # this transfer was cancelled (round closed; ticket
                # revoked, EF mass restored). A newer dispatch may
                # already own the in-flight slot — leave it alone.
                if entry is None:
                    self.busy = False
                return
            rec["ev"] = None         # fired: break the cycle (_finish)
            self._inflight.pop(server_pointer)
            if self._conv.get(server_pointer) is rec:
                self._conv.pop(server_pointer)
            if self.profile.failed:      # died mid-transmit
                self.warehouse.revoke_ticket(ticket)
                link.restore_uplink(up)
                self.busy = False
                return
            self.busy = False
            on_done(TrainResult(self.worker_id, ticket, base_version,
                                epochs, self.profile.n_batches, t_train,
                                t_up=t_up, up_bytes=up.wire_bytes))
        if resumed:
            # the send was already booked by the pre-snapshot transmit();
            # re-create only the delivery event
            rec["ev"] = self._sched_delivery(link, up, _send, at_abs, "up")
        else:
            rec["ev"] = transmit(self.loop, link, up, delay, _send,
                                 direction="up")

    # --- checkpoint/resume ---
    def _sched_delivery(self, link: Link, payload: Payload, deliver,
                        t_abs: float, direction: str):
        return resume_transmit(self.loop, link, payload, t_abs, deliver,
                               direction)

    def resume_conversation(self, server_pointer: Pointer, link: Link,
                            on_done, rec: dict, t_abs: float):
        """Re-create one snapshotted in-flight leg.  Consumes exactly one
        ``loop.schedule`` call, so the restore driver's sorted
        (time, seq) replay preserves the original tie-break order; the
        serialized absolute deadline is replayed exactly (schedule_abs)."""
        phase = rec["phase"]
        self.busy = True
        self._conv[server_pointer] = rec
        if phase == "fetch":
            down = rec["down"]
            self._fetching[server_pointer] = (down, link)
            rec["ev"] = self._sched_delivery(
                link, down,
                lambda: self._fetch_done(server_pointer, down,
                                         rec["base_version"],
                                         rec["epochs"], link, on_done),
                t_abs, "down")
        elif phase == "train_fast":
            self._schedule_finish(server_pointer, link, on_done, rec, 0.0,
                                  at_abs=t_abs)
        elif phase == "train":
            self._schedule_train_send(server_pointer, link, on_done, rec,
                                      0.0, at_abs=t_abs)
        elif phase == "send":
            self._inflight[server_pointer] = (rec["ticket"], rec["up"],
                                              link)
            self._schedule_send(server_pointer, link, on_done, rec, 0.0,
                                resumed=True, at_abs=t_abs)
        else:                            # pragma: no cover
            raise ValueError(f"unknown conversation phase: {phase!r}")
