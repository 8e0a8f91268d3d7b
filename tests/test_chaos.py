"""Seeded chaos tier: kill/recover/loss schedules over every topology
shape, closed by the global invariant auditor.

Each case is one deterministic ``ChaosSchedule`` — per-tier link loss
(drop/duplicate with retransmit/backoff), worker kills (with recovery),
leaf kills, root kills — applied to a small real FL run, after which
``audit_chaos_run`` must close the books: history byte counters against
the delivery ledger, EF revert chains against in-flight dispatches,
warehouse tickets against in-flight uplinks, per-receiver version
monotonicity, and delta (not raw) resume after a root failover.  The
matrix spans worker/leaf/root kills x loss up to 20% x sync/async x
1x1..1x4 topologies, >= 20 seeded schedules.

Root-failover semantics get dedicated deterministic tests (kill pinned
right after the first merge instead of sampled), plus the max_events
truncation guard of the event loop.
"""
import pytest

from repro.core import TABLE_4_1, make_setup
from repro.core.events import EventLoop
from repro.core.topology import parse_topology, run_fl_topology
from repro.runtime.faults import ChaosSchedule, audit_chaos_run

SETUP_KW = dict(seed=0, noise=0.25, batch_size=32, het="strong")
EP, ROUNDS = 2, 3

# (topology, mode/push, run kwargs, chaos kwargs) — seeds are distinct so
# every case is a different sampled schedule; kills land inside the
# ~1.1-simulated-second runs (horizon 1.0)
CHAOS = dict(horizon=1.0, recover_after=0.3)
MATRIX = [
    # 1x1 passthrough: worker tier only (no server wire / root to kill)
    ("1x1", "sync", dict(), dict(seed=0, drop_p=0.1, n_worker_kills=1)),
    ("1x1", "sync", dict(transport="raw"),
     dict(seed=1, drop_p=0.2, n_worker_kills=2)),
    ("1x1", "async", dict(), dict(seed=2, drop_p=0.1, n_worker_kills=1)),
    ("1x1", "async", dict(transport="int8"),
     dict(seed=3, drop_p=0.2, dup_p=0.1, n_worker_kills=1,
          worker_recover=False)),
    # 1x2: root kills (failover promotes the surviving leaf) + leaf kills
    ("1x2", "sync", dict(), dict(seed=4, drop_p=0.1, kill_root=True)),
    ("1x2", "sync", dict(server_codec="topk_ef+int8"),
     dict(seed=5, drop_p=0.2, n_leaf_kills=1)),
    ("1x2", "async", dict(), dict(seed=6, drop_p=0.1, kill_root=True)),
    ("1x2", "async", dict(transport="raw"),
     dict(seed=7, drop_p=0.2, n_leaf_kills=1, n_worker_kills=1)),
    ("1x2", "sync", dict(), dict(seed=16, drop_p=0.05, dup_p=0.2,
                                 n_worker_kills=1)),
    ("1x2", "async", dict(server_codec="topk_ef+int8"),
     dict(seed=17, drop_p=0.2, dup_p=0.1, kill_root=True)),
    # 1x3
    ("1x3", "sync", dict(), dict(seed=8, drop_p=0.1, kill_root=True,
                                 n_worker_kills=1)),
    ("1x3", "async", dict(), dict(seed=9, drop_p=0.15, kill_root=True)),
    ("1x3", "sync", dict(server_codec="topk_ef+int8"),
     dict(seed=10, drop_p=0.2, n_leaf_kills=1, kill_root=True)),
    ("1x3", "async", dict(), dict(seed=11, drop_p=0.0, kill_root=True)),
    ("1x3", "sync", dict(transport="int8"),
     dict(seed=19, drop_p=0.2, dup_p=0.05, n_leaf_kills=1,
          n_worker_kills=1)),
    # 1x4 (loss at the 20% ceiling)
    ("1x4", "sync", dict(), dict(seed=12, drop_p=0.1, kill_root=True)),
    ("1x4", "async", dict(), dict(seed=13, drop_p=0.2, n_leaf_kills=2)),
    ("1x4", "sync", dict(server_codec="topk_ef+int8"),
     dict(seed=14, drop_p=0.2, n_worker_kills=2, kill_root=True)),
    ("1x4", "async", dict(), dict(seed=15, drop_p=0.1, kill_root=True,
                                  n_worker_kills=1)),
    ("1x4", "async", dict(), dict(seed=18, drop_p=0.15, n_leaf_kills=1,
                                  kill_root=True)),
    ("1x4", "sync", dict(transport="raw"),
     dict(seed=20, drop_p=0.2, kill_root=True, n_leaf_kills=1)),
]


def _run_chaos(topology, mode, run_kw, chaos_kw):
    run_kw = dict(run_kw)
    topo_kw = {}
    for k in ("server_codec", "server_codec_down", "root_failover"):
        if k in run_kw:
            topo_kw[k] = run_kw.pop(k)
    if topology != "1x1":
        topo_kw.setdefault("push", mode)
    run_kw.setdefault("transport", "topk_ef+int8")
    if run_kw["transport"] != "raw":
        run_kw.setdefault("transport_frac", 0.1)
    sched = ChaosSchedule(**{**CHAOS, **chaos_kw})
    setup = make_setup(TABLE_4_1["mnist_even"], **SETUP_KW)
    res = run_fl_topology(
        setup, topology=parse_topology(topology, **topo_kw), mode=mode,
        selector="all", epochs_per_round=EP, max_rounds=ROUNDS,
        on_build=sched.apply, **run_kw)
    return res, sched


@pytest.mark.parametrize("topology,mode,run_kw,chaos_kw", MATRIX)
def test_chaos_schedule_books_close(topology, mode, run_kw, chaos_kw):
    res, sched = _run_chaos(topology, mode, run_kw, chaos_kw)
    stats = audit_chaos_run(res.topology)
    assert sched.events or sched.drop_p >= 0  # schedule actually sampled
    # the run produced real history under chaos (at least the seed point)
    assert all(len(h) >= 1 for h in res.leaf_histories.values())


def test_chaos_lossy_runs_actually_retransmit():
    """At 20% drop across hundreds of copies, the retransmit machinery
    must fire and be visible on the history points (counted separately
    from the byte counters)."""
    res, _ = _run_chaos("1x2", "sync", {},
                        dict(seed=42, drop_p=0.2, dup_p=0.1))
    stats = audit_chaos_run(res.topology)
    assert stats["retransmits"] > 0
    for h in res.leaf_histories.values():
        assert h[-1].retransmits >= 0
    assert any(h[-1].retransmits > 0
               for h in res.leaf_histories.values())


def test_lossy_link_bandwidth_estimate_converges_to_channel_rate():
    """Regression (retransmit-inflated bandwidth samples): on a 20%-loss
    link every ``observe_transmit`` sample must be the delivered copy's
    one-transmission wire time, so the estimator's bandwidth equals the
    channel rate EXACTLY — never rate/(1-p)-with-backoff.  A poisoned
    estimate would compound: it feeds retransmit timeouts, eq-3.4
    selection budgets, and the auto codec's per-link choice."""
    res, _ = _run_chaos("1x1", "sync", {}, dict(seed=33, drop_p=0.2))
    stats = audit_chaos_run(res.topology)
    assert stats["retransmits"] > 0          # the lossy path really ran
    checked = 0
    for lf in res.topology.leaves.values():
        srv = lf.server
        for w in srv.workers.values():
            bw = srv.est.bandwidth(w.worker_id)
            if bw is None:                   # never delivered a response
                continue
            assert bw == pytest.approx(w.profile.bandwidth, rel=1e-12), \
                (w.worker_id, bw, w.profile.bandwidth)
            checked += 1
    assert checked > 0


def test_lossless_chaos_ledger_closes_exactly():
    """drop_p=0 still engages the full channel + ledger machinery: every
    sent payload is delivered exactly once and the books close with zero
    retransmits."""
    res, _ = _run_chaos("1x2", "sync", {}, dict(seed=21, drop_p=0.0,
                                                dup_p=0.0))
    stats = audit_chaos_run(res.topology)
    assert stats["retransmits"] == 0
    for lf in res.topology.leaves.values():
        aud = lf.server.transport.audit
        assert aud.sent_count == aud.delivered_count
        assert aud.dup_count == {"up": 0, "down": 0}


# ---------------- deterministic root-failover semantics ----------------

def _kill_root_after_merge(version: int, delay: float = 1e-3):
    """on_build hook: kill the root ``delay`` after global ``version``
    merges — deterministic mid-run placement, after the fan-outs of that
    merge have (tiny wire) arrived and advanced the acked bases."""
    def hook(topo):
        orig = topo._merge

        def merge_then_kill():
            orig()
            if topo.version == version and not topo.done:
                topo.loop.schedule(delay, topo.kill_root)
        topo._merge = merge_then_kill
    return hook


def test_root_failover_resumes_delta():
    """Root death after the first merge: the senior surviving leaf is
    promoted, every survivor is re-provisioned with a DELTA against its
    acked base (no raw re-sync storm), and the run continues to new
    global versions under the promoted root."""
    setup = make_setup(TABLE_4_1["mnist_even"], **SETUP_KW)
    res = run_fl_topology(
        setup, topology=parse_topology("1x3"), mode="sync",
        selector="all", epochs_per_round=EP, max_rounds=ROUNDS,
        transport="topk_ef+int8", transport_frac=0.1,
        on_build=_kill_root_after_merge(1))
    topo = res.topology
    assert topo.failovers == 1
    assert topo.failover_dispatches, "promotion re-provisioned nobody"
    for lid, codec, had_base in topo.failover_dispatches:
        assert had_base, f"{lid} lost its acked base across failover"
        assert codec != "raw", f"{lid} got a raw re-sync after failover"
    # the role continued: versions advanced past the death point
    assert topo.version > 1
    assert res.root_history[-1].version == topo.version
    audit_chaos_run(topo)


def test_root_failover_preserves_counters_and_history():
    """Byte counters, retransmit counter, and the history sequence carry
    over the promotion — the root is a role, not a process."""
    setup = make_setup(TABLE_4_1["mnist_even"], **SETUP_KW)
    res = run_fl_topology(
        setup, topology=parse_topology("1x2"), mode="sync",
        selector="all", epochs_per_round=EP, max_rounds=ROUNDS,
        transport="topk_ef+int8", transport_frac=0.1,
        on_build=_kill_root_after_merge(1))
    topo = res.topology
    assert topo.failovers == 1
    hist = res.root_history
    # one unbroken monotone history across the failover
    for prev, cur in zip(hist, hist[1:]):
        assert cur.version == prev.version + 1
        assert cur.up_bytes >= prev.up_bytes
        assert cur.down_bytes >= prev.down_bytes
    audit_chaos_run(topo)


def test_root_failover_off_ends_run():
    """Without root_failover, root death rolls back in-flight transfers
    and ends the run at the last merged version."""
    setup = make_setup(TABLE_4_1["mnist_even"], **SETUP_KW)
    res = run_fl_topology(
        setup, topology=parse_topology("1x2", root_failover=False),
        mode="sync", selector="all", epochs_per_round=EP,
        max_rounds=ROUNDS, transport="topk_ef+int8", transport_frac=0.1,
        on_build=_kill_root_after_merge(1))
    topo = res.topology
    assert topo.failovers == 0
    assert topo.done
    assert res.root_history[-1].version == 1
    audit_chaos_run(topo)


def test_kill_root_under_loss_books_still_close():
    """Failover while the server wire is lossy: retransmit timers and
    stale copies of pre-death payloads must all be absorbed by the
    sequence dedup / inflight guards."""
    sched = ChaosSchedule(seed=77, drop_p=0.2, dup_p=0.1, horizon=1.0,
                          n_worker_kills=0)

    def on_build(topo):
        sched.apply(topo)
        _kill_root_after_merge(1)(topo)

    setup = make_setup(TABLE_4_1["mnist_even"], **SETUP_KW)
    res = run_fl_topology(
        setup, topology=parse_topology("1x3"), mode="async",
        selector="all", epochs_per_round=EP, max_rounds=ROUNDS,
        transport="topk_ef+int8", transport_frac=0.1, on_build=on_build)
    assert res.topology.failovers == 1
    audit_chaos_run(res.topology)


def test_root_failover_carries_server_opt_state():
    """PR-10 fix: the root-carried server optimizer's vectors must ride
    the promotion like the ack registry — the promoted root keeps taking
    REAL optimizer steps (momentum state non-null, next merge transforms
    the install) instead of silently reverting to plain FedAvg, and the
    books still close."""
    setup = make_setup(TABLE_4_1["mnist_even"], **SETUP_KW)
    res = run_fl_topology(
        setup, topology=parse_topology("1x3"), mode="sync",
        selector="all", epochs_per_round=EP, max_rounds=ROUNDS,
        transport="topk_ef+int8", transport_frac=0.1,
        server_opt="fedavgm", server_opt_kw={"momentum": 0.9},
        on_build=_kill_root_after_merge(1))
    topo = res.topology
    assert topo.failovers == 1
    assert topo.version > 1                 # merges continued post-death
    opt = topo.server_opt
    assert opt is not None and opt.momentum == 0.9
    # pre-death merges built momentum; post-death merges kept using it
    assert opt._m is not None
    # the same object is wired into the flat substrate's merge tail (the
    # substrate survives _promote_root — state rides structurally)
    assert topo._flat.server_opt is opt
    # rebase dropped the stale anchor; the post-failover step re-anchored
    # on the promoted model (prev tracking alive again)
    assert opt._prev_tree is topo.weights
    audit_chaos_run(topo)


def test_kill_root_under_loss_with_server_opt_books_close():
    """Sampled chaos + root kill with FedAdam at the root: adaptive-step
    state must not break the delivery ledger or version monotonicity."""
    sched = ChaosSchedule(seed=88, drop_p=0.15, dup_p=0.05, horizon=1.0,
                          n_worker_kills=1)

    def on_build(topo):
        sched.apply(topo)
        _kill_root_after_merge(1)(topo)

    setup = make_setup(TABLE_4_1["mnist_even"], **SETUP_KW)
    res = run_fl_topology(
        setup, topology=parse_topology("1x3"), mode="sync",
        selector="all", epochs_per_round=EP, max_rounds=ROUNDS,
        transport="topk_ef+int8", transport_frac=0.1,
        server_opt="fedadam", server_opt_kw={"lr": 0.05},
        on_build=on_build)
    assert res.topology.failovers == 1
    audit_chaos_run(res.topology)


def test_kill_root_on_passthrough_raises():
    setup = make_setup(TABLE_4_1["mnist_even"], **SETUP_KW)

    def on_build(topo):
        with pytest.raises(ValueError):
            topo.kill_root()
    run_fl_topology(setup, topology="1x1", mode="sync", selector="all",
                    epochs_per_round=EP, max_rounds=1, on_build=on_build)


# ---------------- max_events truncation guard ----------------

def test_event_loop_records_exhaustion():
    loop = EventLoop()

    def reschedule():
        loop.schedule(1.0, reschedule)
    loop.schedule(0.0, reschedule)
    loop.run(max_events=10)
    assert loop.exhausted
    # a completed run clears the flag
    done_loop = EventLoop()
    done_loop.schedule(0.0, lambda: None)
    done_loop.run(max_events=10)
    assert not done_loop.exhausted


def test_run_fl_topology_raises_on_truncation():
    setup = make_setup(TABLE_4_1["mnist_even"], **SETUP_KW)
    with pytest.raises(RuntimeError, match="max_events"):
        run_fl_topology(setup, topology="1x2", mode="sync",
                        selector="all", epochs_per_round=EP,
                        max_rounds=ROUNDS, max_events=5)
