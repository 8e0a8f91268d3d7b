"""Durable federation: crash-consistent checkpoint/resume of the FULL
simulation state (tentpole of the durable-runs PR).

The correctness bar everywhere is *bit-exactness*: an uninterrupted run's
history must equal, float-hex-identically, the history of a run killed at
a checkpoint boundary plus its resumed continuation.  The pinned-fixture
split cases live in test_golden_histories.py; this file covers the
non-fixture matrix (async x compressed/auto, real 1x2 topologies with
both push disciplines), the checkpoint-manager bugfixes (stale ``.tmp``
sweep, readable-aware GC, ``keep<=0``), the ``max_events`` plumbing, and
the chaos tier: a run whose PROCESS is SIGKILLed mid-run must resume
from the last published snapshot and ``audit_chaos_run`` must still
close the books.
"""
import os
import pickle
import signal
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, FederationSnapshot
from repro.core import TABLE_4_1, make_setup, run_fl
from repro.core.topology import (TopologyConfig, build_topology,
                                 parse_topology, run_fl_topology)
from repro.runtime.faults import ChaosSchedule, FaultInjector, \
    audit_chaos_run

SETUP_KW = dict(seed=0, noise=0.25, batch_size=32, het="strong")
EP, ROUNDS = 2, 3


def _fresh():
    return make_setup(TABLE_4_1["mnist_even"], **SETUP_KW)


def _rec(history):
    return [(p.time.hex(), p.version, float(p.accuracy).hex(), p.n_updates,
             p.selected, p.up_bytes, p.down_bytes) for p in history]


def _allrec(res):
    out = {"root": _rec(res.root_history)}
    out.update({lid: _rec(h) for lid, h in res.leaf_histories.items()})
    return out


# ---------------- non-fixture bit-exact split matrix ----------------

RUN_MATRIX = [
    ("async", dict(transport="topk_ef+int8", transport_frac=0.1)),
    ("async", dict(transport="auto")),
    ("async_delta", dict(transport="topk_ef+int8", transport_frac=0.1)),
    ("async_delta", dict(transport="auto")),
]
_MODE_KW = {
    "async": dict(mode="async", selector="all", async_alpha=0.9,
                  async_latest_table=False, aggregator="linear"),
    "async_delta": dict(mode="async", selector="all", async_delta=True),
}


@pytest.mark.parametrize("mname,tkw", RUN_MATRIX,
                         ids=[f"{m}-{t['transport']}"
                              for m, t in RUN_MATRIX])
def test_run_fl_split_matches_uninterrupted(mname, tkw, tmp_path):
    h_full = run_fl(_fresh(), epochs_per_round=EP, max_rounds=ROUNDS,
                    **_MODE_KW[mname], **tkw)
    d = str(tmp_path / "ckpt")
    run_fl(_fresh(), epochs_per_round=EP, max_rounds=ROUNDS,
           **_MODE_KW[mname], **tkw, checkpoint_every=1,
           checkpoint_dir=d, stop_after_checkpoints=1)
    h_res = run_fl(_fresh(), epochs_per_round=EP, max_rounds=ROUNDS,
                   **_MODE_KW[mname], **tkw, checkpoint_dir=d, resume=True)
    assert _rec(h_res) == _rec(h_full)


TOPO_MATRIX = [("sync", "raw"), ("sync", "topk_ef+int8"),
               ("async", "raw"), ("async", "topk_ef+int8")]


@pytest.mark.parametrize("push,transport", TOPO_MATRIX,
                         ids=[f"push_{p}-{t}" for p, t in TOPO_MATRIX])
def test_topology_split_matches_uninterrupted(push, transport, tmp_path):
    """Full 1x2 hierarchical state (root weights, server<->server acks,
    leaf push/fan legs, per-leaf servers) through a kill+resume."""
    cfg = TopologyConfig(n_leaves=2, push=push)
    tkw = dict(transport=transport)
    if transport != "raw":
        tkw["transport_frac"] = 0.1
    full = run_fl_topology(_fresh(), topology=cfg, mode="sync",
                           epochs_per_round=EP, max_rounds=ROUNDS, **tkw)
    d = str(tmp_path / "ckpt")
    run_fl_topology(_fresh(), topology=cfg, mode="sync",
                    epochs_per_round=EP, max_rounds=ROUNDS, **tkw,
                    checkpoint_every=1, checkpoint_dir=d,
                    stop_after_checkpoints=1)
    res = run_fl_topology(_fresh(), topology=cfg, mode="sync",
                          epochs_per_round=EP, max_rounds=ROUNDS, **tkw,
                          checkpoint_dir=d, resume=True)
    assert _allrec(res) == _allrec(full)


def test_resume_without_checkpoint_raises(tmp_path):
    with pytest.raises(FileNotFoundError, match="no readable checkpoint"):
        run_fl(_fresh(), epochs_per_round=EP, max_rounds=ROUNDS,
               mode="sync", checkpoint_dir=str(tmp_path / "empty"),
               resume=True)


def test_checkpoint_requires_dir():
    with pytest.raises(ValueError, match="checkpoint_dir"):
        run_fl(_fresh(), epochs_per_round=EP, max_rounds=ROUNDS,
               mode="sync", checkpoint_every=1)


# ---------------- snapshot round-trip (non-property spelling) ----------

def _residual_norms(tr_img):
    return sorted((li["tok"], float(np.linalg.norm(li["residual"])))
                  for li in tr_img["links"].values()
                  if li["residual"] is not None)


def test_snapshot_pickle_roundtrip_counters_exact(tmp_path):
    """capture -> pickle -> restore into a fresh build -> capture again:
    byte counters, link bases and EF-residual norms survive exactly.
    (The hypothesis-driven spelling of this property lives in
    test_fl_properties.py; this one runs in the tier-1 suite.)"""
    d = str(tmp_path / "ckpt")
    run_fl(_fresh(), epochs_per_round=EP, max_rounds=ROUNDS,
           mode="async", selector="all", async_delta=True,
           transport="topk_ef+int8", transport_frac=0.1,
           checkpoint_every=1, checkpoint_dir=d, stop_after_checkpoints=1)
    _, snap, _ = CheckpointManager(d).restore_latest()
    snap2 = pickle.loads(pickle.dumps(snap))

    from repro.core.experiment import build_experiment
    loop, server = build_experiment(
        _fresh(), epochs_per_round=EP, max_rounds=ROUNDS,
        mode="async", selector="all", async_delta=True,
        transport="topk_ef+int8", transport_frac=0.1)
    snap2.restore_run(loop, server)
    snap3 = FederationSnapshot.capture_run(loop, server)

    s_img, s3_img = snap.state["server"], snap3.state["server"]
    assert s3_img["total_up"] == s_img["total_up"]
    assert s3_img["total_down"] == s_img["total_down"]
    assert s3_img["version"] == s_img["version"]
    t_img, t3_img = s_img["transport"], s3_img["transport"]
    assert _residual_norms(t3_img) == _residual_norms(t_img)
    assert sorted((wid, li["tx_base"] is not None)
                  for wid, li in t3_img["links"].items()) \
        == sorted((wid, li["tx_base"] is not None)
                  for wid, li in t_img["links"].items())
    # pending events survive as the same (kind, t) multiset (seq numbers
    # are loop-local and legitimately renumbered by the replay)
    assert sorted((r["kind"], r["t"]) for r in snap3.events) \
        == sorted((r["kind"], r["t"]) for r in snap.events)
    assert snap3.clock == snap.clock


def test_snapshot_refuses_failed_over_root(tmp_path):
    """Root-failover state is explicitly out of the snapshot contract:
    capturing after a promotion must refuse loudly, not corrupt."""
    cfg = parse_topology("1x2", push="sync", root_failover=True)
    loop, topo = build_topology(_fresh(), topology=cfg, mode="sync",
                                epochs_per_round=EP, max_rounds=ROUNDS)
    topo.failovers = 1    # simulate a promoted root
    with pytest.raises(NotImplementedError, match="failed-over root"):
        FederationSnapshot.capture_topology(loop, topo)


# ---------------- checkpoint-manager bugfixes ----------------

def test_stale_tmp_swept_on_init_and_save(tmp_path):
    """A save that crashed between mkstemp and the atomic publish leaves
    a ``*.tmp`` orphan; both construction and the next save sweep it."""
    (tmp_path / "stale_crash_a.tmp").write_bytes(b"partial write")
    mgr = CheckpointManager(str(tmp_path), keep=2)
    assert list(tmp_path.glob("*.tmp")) == []
    # plant another after construction: the next save must sweep it too
    (tmp_path / "stale_crash_b.tmp").write_bytes(b"partial write")
    mgr.save(1, {"x": np.ones(2)})
    assert list(tmp_path.glob("*.tmp")) == []
    step, state, _ = mgr.restore_latest()
    assert step == 1 and np.array_equal(state["x"], np.ones(2))


def test_gc_never_counts_unreadable_toward_keep(tmp_path):
    """An unreadable (corrupt) snapshot must not evict the checkpoints a
    restore actually needs: with keep=2 and the newest file corrupt,
    BOTH readable steps survive GC."""
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save(1, {"x": np.ones(1)})
    mgr.save(2, {"x": np.full(1, 2.0)})
    mgr.save(3, {"x": np.full(1, 3.0)})
    mgr._path(3).write_bytes(b"\x00corrupt")      # newest unreadable
    mgr.save(4, {"x": np.full(1, 4.0)})           # triggers GC
    steps = mgr.steps()
    assert 2 in steps and 4 in steps, \
        f"GC evicted a readable step a restore needs: {steps}"
    step, state, _ = mgr.restore_latest()
    assert step == 4 and np.array_equal(state["x"], np.full(1, 4.0))


def test_gc_keep_nonpositive_keeps_everything(tmp_path):
    """keep<=0 used to slice ``ckpts[:-0] == ckpts`` and delete every
    checkpoint; it now disables retention entirely."""
    for keep in (0, -1):
        d = tmp_path / f"k{keep}"
        mgr = CheckpointManager(str(d), keep=keep)
        for s in (1, 2, 3, 4, 5):
            mgr.save(s, {"x": np.zeros(1)})
        assert mgr.steps() == [1, 2, 3, 4, 5], \
            f"keep={keep} dropped checkpoints"


# ---------------- max_events plumbing ----------------

def test_max_events_exposed_and_enforced():
    with pytest.raises(RuntimeError, match="max_events=7"):
        run_fl(_fresh(), epochs_per_round=EP, max_rounds=ROUNDS,
               mode="sync", max_events=7)
    with pytest.raises(RuntimeError, match="max_events=7"):
        run_fl_topology(_fresh(), topology=parse_topology("1x2"),
                        mode="sync", epochs_per_round=EP,
                        max_rounds=ROUNDS, max_events=7)


def test_max_events_budget_spans_checkpoint_segments(tmp_path):
    """The budget is accounted ACROSS checkpoint segments — a
    checkpointed run gets the same total as an uninterrupted one, so a
    budget that starves the full run (30 events for this config) still
    starves the segmented one — segmentation must not reset the meter."""
    with pytest.raises(RuntimeError, match="max_events=25"):
        run_fl(_fresh(), epochs_per_round=EP, max_rounds=ROUNDS,
               mode="sync", max_events=25, checkpoint_every=1,
               checkpoint_dir=str(tmp_path / "c"))


# ---------------- chaos tier: SIGKILL the process, resume, audit -------

_CHAOS_KW = dict(seed=11, drop_p=0.2, dup_p=0.1, horizon=1.0,
                 recover_after=0.3, n_worker_kills=1)
_CHAOS_RUN_KW = dict(mode="sync", selector="all", epochs_per_round=2,
                     max_rounds=4, transport="topk_ef+int8",
                     transport_frac=0.1)

_CHILD_SRC = textwrap.dedent("""
    import sys
    sys.path.insert(0, {src!r})
    from repro.core import TABLE_4_1, make_setup
    from repro.core.topology import parse_topology, run_fl_topology
    from repro.runtime.faults import ChaosSchedule
    setup = make_setup(TABLE_4_1["mnist_even"], seed=0, noise=0.25,
                       batch_size=32, het="strong")
    sched = ChaosSchedule(**{chaos_kw!r})
    run_fl_topology(setup, topology=parse_topology("1x2", push="sync"),
                    on_build=sched.apply, checkpoint_every=1,
                    checkpoint_dir={ckpt_dir!r}, **{run_kw!r})
    print("CHILD_FINISHED", flush=True)
""")


def _reinject_chaos(loop, topo, cfg):
    """Recompute the deterministic chaos schedule on a throwaway build
    and re-schedule ONLY the events still in the restored run's future.
    Re-running ``sched.apply`` on the live topology would be wrong twice
    over: past kill events would rewind the clock when they fire, and
    ``inject_link_reliability`` would wipe the restored channel ledgers.
    """
    scratch = ChaosSchedule(**_CHAOS_KW)
    _, throwaway = build_topology(_fresh(), topology=cfg, **_CHAOS_RUN_KW)
    for kind, t, arg in scratch.apply(throwaway):
        if t <= loop.now:
            continue        # already burned into the snapshot's history
        if kind in ("kill_worker", "recover_worker"):
            srv = next(lf.server for lf in topo.leaves.values()
                       if arg in lf.server.workers)
            inj = FaultInjector(loop, srv)
            (inj.kill_at if kind == "kill_worker"
             else inj.recover_at)(t, arg)
        elif kind == "kill_leaf":
            topo.kill_leaf_at(t, arg)
        else:                     # pragma: no cover
            raise AssertionError(f"unexpected chaos event {kind!r} "
                                 "(kill_root runs use kill_root=False)")


def test_chaos_process_kill_then_resume_books_close(tmp_path):
    """The full durability story: a lossy chaos run is SIGKILLed as a
    PROCESS mid-run; the parent resumes from whatever snapshot was last
    durably published (any half-written ``.tmp`` is invisible), replays
    the remaining chaos schedule, and ``audit_chaos_run`` still closes
    the books on the stitched-together run."""
    d = tmp_path / "ckpt"
    src = str(Path(__file__).resolve().parents[1] / "src")
    child_py = tmp_path / "child.py"
    child_py.write_text(_CHILD_SRC.format(
        src=src, chaos_kw=_CHAOS_KW, ckpt_dir=str(d), run_kw=_CHAOS_RUN_KW))
    # a CPU durability test: the parent may hold the one accelerator the
    # child would otherwise wait for
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen([sys.executable, str(child_py)], env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT)
    try:
        # SIGKILL as soon as the first snapshot is durably on disk
        deadline = time.time() + 120
        while time.time() < deadline:
            if d.exists() and list(d.glob("ckpt_*.pkl")):
                break
            if proc.poll() is not None:
                out = proc.stdout.read().decode()
                raise AssertionError(
                    f"child exited before first checkpoint:\n{out}")
            time.sleep(0.05)
        else:
            raise AssertionError("child never published a checkpoint")
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == -signal.SIGKILL

    cfg = parse_topology("1x2", push="sync")
    loop, topo = build_topology(_fresh(), topology=cfg, **_CHAOS_RUN_KW)
    got = CheckpointManager(str(d)).restore_latest()
    assert got is not None, "no readable checkpoint survived the SIGKILL"
    _, snap, _ = got
    snap.restore_topology(loop, topo)
    _reinject_chaos(loop, topo, cfg)
    loop.run(max_events=200_000)
    topo.finalize()
    stats = audit_chaos_run(topo)          # must not raise: books closed
    assert stats["retransmits"] >= 0
    for lid, lf in topo.leaves.items():
        assert len(lf.server.history) >= 1
        # the resumed run made real forward progress past the snapshot
        assert lf.server.version >= snap.state["servers"][lid]["version"]


def test_chaos_in_process_kill_resume_with_cancelled_legs(tmp_path):
    """In-process spelling with a seed whose snapshot catches lossy legs
    mid-flight (exercising cancel-with-credit + re-kick), killed after
    TWO checkpoints so the resume starts from the later one."""
    d = str(tmp_path / "ckpt")
    cfg = parse_topology("1x2", push="sync")
    sched = ChaosSchedule(**_CHAOS_KW)
    run_fl_topology(_fresh(), topology=cfg, on_build=sched.apply,
                    checkpoint_every=1, checkpoint_dir=d,
                    stop_after_checkpoints=2, **_CHAOS_RUN_KW)
    loop, topo = build_topology(_fresh(), topology=cfg, **_CHAOS_RUN_KW)
    _, snap, _ = CheckpointManager(d).restore_latest()
    snap.restore_topology(loop, topo)
    _reinject_chaos(loop, topo, cfg)
    loop.run(max_events=200_000)
    topo.finalize()
    audit_chaos_run(topo)
    for lf in topo.leaves.values():
        assert lf.server.history[-1].version >= _CHAOS_RUN_KW["max_rounds"]
