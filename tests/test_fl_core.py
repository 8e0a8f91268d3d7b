"""Unit tests for the FL core: aggregation algorithms (eqs 2.1-2.7), worker
selection (Algorithms 1 & 2), eq-3.4 estimation, warehouse/pointer
semantics. Hypothesis property tests live in test_fl_properties.py (guarded
with importorskip — hypothesis is a dev-only extra)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregation as agg, flatbuf
from repro.core.estimator import TimeEstimator, WorkerProfile
from repro.core.selection import (RMinRMaxSelector, TimeBasedSelector,
                                  AllSelector)
from repro.core.warehouse import DataWarehouse, DiskStorage, Pointer


def _tree(rng, scale=1.0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(rng))
    return {"a": jax.random.normal(k1, (7, 5)) * scale,
            "b": {"c": jax.random.normal(k2, (11,)) * scale}}


# ---------------- aggregation ----------------

def _merge(name, ups):
    """Replace-on-aggregate (alpha 1) of ``ups`` under aggregator ``name``
    through the packed merge the servers run."""
    trees = [u.weights for u in ups]
    return flatbuf.FlatServerState(trees[0]).merge(
        trees[0], trees, agg.update_weights(name, ups), alpha=1.0)


def test_fedavg_identity():
    t = _tree(0)
    out = _merge("fedavg", [agg.WorkerUpdate(weights=t) for _ in range(4)])
    assert all(jnp.allclose(a, b, atol=1e-6)
               for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(t)))


def test_fedavg_mean_of_two():
    t1, t2 = _tree(1), _tree(2)
    out = _merge("fedavg", [agg.WorkerUpdate(weights=t1),
                            agg.WorkerUpdate(weights=t2)])
    expect = jax.tree.map(lambda a, b: (a + b) / 2, t1, t2)
    assert all(jnp.allclose(a, b, atol=1e-6)
               for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(expect)))


def test_weighted_equals_fedavg_when_uniform():
    trees = [_tree(i) for i in range(3)]
    ups = [agg.WorkerUpdate(weights=t, staleness=0, n_data=1) for t in trees]
    a = _merge("fedavg", ups)
    b = _merge("linear", ups)
    assert all(jnp.allclose(x, y, atol=1e-6)
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_kernel_fedavg_matches_tree_fedavg():
    from repro.kernels.ops import fedavg_aggregate
    trees = [_tree(i) for i in range(3)]
    ups = [agg.WorkerUpdate(weights=t) for t in trees]
    a = _merge("fedavg", ups)
    b = fedavg_aggregate(trees, jnp.ones((3,)), interpret=True)
    assert all(jnp.allclose(x, y, atol=1e-5)
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------- estimation (eq 3.4) ----------------

def test_eq34_estimation():
    est = TimeEstimator(server_freq=3.0, t_onebatch_server=0.05)
    p = WorkerProfile("w0", cpu_freq=1.5, cpu_prop=0.5, n_batches=4)
    # per-batch = 0.05 * 3.0 / (1.5*0.5) = 0.2; epoch over 4 batches = 0.8
    assert abs(est.t_one(p) - 0.8) < 1e-9
    est.observe_training("w0", 0.33)
    assert est.t_one(p) == 0.33     # measurement overrides the heuristic


def test_transmit_estimation():
    est = TimeEstimator()
    p = WorkerProfile("w0", bandwidth=10e6)
    assert abs(est.t_transmit(p, 5_000_000) - 0.5) < 1e-9


# ---------------- selection ----------------

def _profiles(freqs):
    return [WorkerProfile(f"w{i}", cpu_freq=f, cpu_prop=1.0, bandwidth=1e9,
                          n_batches=1) for i, f in enumerate(freqs)]


def test_alg1_fastest_always_selected():
    est = TimeEstimator()
    sel = RMinRMaxSelector(est, model_bytes=1000, rmin=5, rmax=5)
    profs = _profiles([3.0, 2.0, 1.0, 0.5])
    chosen = sel.select(profs)
    assert "w0" in chosen                      # fastest satisfies its own bound
    # with rmin == rmax, slow workers are excluded
    assert "w3" not in chosen


def test_alg1_update_diverges_rmin_rmax():
    est = TimeEstimator()
    sel = RMinRMaxSelector(est, model_bytes=1000, rmin=5, rmax=5)
    sel.on_round_end(0.5)                      # accuracy rose from 0
    assert sel.rmin < 5.0 and sel.rmax > 5.0   # eqs 3.1/3.2


def test_alg2_admits_more_workers_as_T_grows():
    est = TimeEstimator()
    profs = _profiles([3.0, 2.0, 1.0, 0.5])
    sel = TimeBasedSelector(est, model_bytes=1000, r=10, T0=0.0, accuracy_threshold=0.01)
    assert sel.select(profs) == []             # T=0 admits nobody
    sel.on_round_end(0.0)                      # no gain -> grow T (eq 3.3)
    s1 = set(sel.select(profs))
    assert len(s1) >= 1
    sel.on_round_end(0.0)
    s2 = set(sel.select(profs))
    assert s1 <= s2 and len(s2) > len(s1)      # monotone admission


def test_alg2_keeps_T_on_accuracy_gain():
    est = TimeEstimator()
    profs = _profiles([3.0, 1.0])
    sel = TimeBasedSelector(est, model_bytes=1000, r=10, T0=0.0,
                            accuracy_threshold=0.01)
    sel.select(profs)
    sel.on_round_end(0.0)
    T_after_open = sel.T
    sel.select(profs)
    sel.on_round_end(0.5)                      # big gain: T must NOT grow
    assert sel.T == T_after_open


def test_failed_workers_never_selected():
    profs = _profiles([3.0, 2.0])
    profs[0].failed = True
    assert AllSelector().select(profs) == ["w1"]
    est = TimeEstimator()
    sel = TimeBasedSelector(est, 1000, r=10, T0=1e9)
    assert "w0" not in sel.select(profs)


class _MutatingBytes:
    """A time-varying BytesSpec (the auto codec's expected_oneway_bytes is
    one): every resolution returns the next value."""

    def __init__(self, *values):
        self.values = list(values)
        self.calls = 0

    def __call__(self):
        self.calls += 1
        v = self.values[0]
        if len(self.values) > 1:
            self.values.pop(0)
        return v


def test_alg2_round_end_prices_bytes_pinned_at_select():
    """Regression (stale-BytesSpec re-pricing): the eq-3.3 budget raise
    must price the SAME bytes as the select that produced the pending
    set.  Pre-fix, _t_total re-resolved the BytesSpec inside
    on_round_end, so a spec that mutated between the calls raised T
    against bytes no select ever saw."""
    est = TimeEstimator()
    profs = _profiles([3.0])
    profs[0].bandwidth = 1e3            # transmit term dominates
    spec = _MutatingBytes(1000, 9_999_000)   # select sees 1000, then grows
    sel = TimeBasedSelector(est, spec, r=0, T0=0.0, accuracy_threshold=0.01)
    assert sel.select(profs) == []           # T=0 admits nobody
    sel.on_round_end(0.0)                    # eq-3.3 raise
    # the raise priced 1000 B at 1e3 B/s = 1.0 s, NOT the mutated value
    assert sel.T == pytest.approx(1.0)
    # and select resolved the spec exactly once for the whole round
    assert spec.calls == 1


def test_alg2_each_select_reresolves_the_spec():
    """Pinning is per round, not forever: the NEXT select re-resolves
    (that is what makes an auto transport's pricing time-varying)."""
    est = TimeEstimator()
    profs = _profiles([3.0])
    profs[0].bandwidth = 1e3
    spec = _MutatingBytes(1000, 2000)
    sel = TimeBasedSelector(est, spec, r=0, T0=10.0)
    assert sel.select(profs) == ["w0"]       # 1.0 s <= 10
    assert sel.select(profs) == ["w0"]       # 2.0 s <= 10
    assert spec.calls == 2


def test_alg1_resolves_bytes_once_per_select():
    """RMinRMaxSelector: one BytesSpec resolution per select, pinned on
    the instance — t_min and t_max must price identical bytes."""
    est = TimeEstimator()
    profs = _profiles([3.0, 1.0])
    spec = _MutatingBytes(1000, 2000)
    sel = RMinRMaxSelector(est, spec, rmin=5, rmax=5)
    sel.select(profs)
    assert spec.calls == 1
    assert sel._pending_bytes == 1000
    sel.on_round_end(0.5)                    # eqs 3.1/3.2: no re-resolve
    assert spec.calls == 1
    sel.select(profs)
    assert spec.calls == 2 and sel._pending_bytes == 2000


# ---------------- warehouse / pointers ----------------

def test_warehouse_roundtrip_and_tickets():
    wh = DataWarehouse()
    uid = wh.put({"x": 1})
    assert wh.get(uid) == {"x": 1}
    cred = wh.issue_ticket(uid)
    assert wh.redeem_ticket(cred) == {"x": 1}
    with pytest.raises(KeyError):
        wh.redeem_ticket(cred)                 # one-time credential


def test_warehouse_disk_storage(tmp_path):
    wh = DataWarehouse()
    wh.add_storage("disk", DiskStorage(str(tmp_path)))
    uid = wh.put(np.arange(10), storage="disk")
    assert np.array_equal(wh.get(uid), np.arange(10))
    wh.delete(uid)
    assert uid not in wh


def test_pointer_identity():
    p1 = Pointer("worker://w0", "obj1")
    p2 = Pointer("worker://w0", "obj1")
    assert p1 == p2 and str(p1) == "worker://w0/obj1"


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_a_trained_model_is_freed_without_the_cycle_collector(mode):
    """A finished training leaves no reference cycle that holds its input
    model: with the cyclic collector off, the models alive after many
    rounds are the setup's, the server's and the few in flight."""
    import gc
    from repro.core.experiment import build_experiment, make_setup
    setup = make_setup([1] * 6, n_test=64, seed=3)
    loop, server = build_experiment(setup, mode=mode, epochs_per_round=1,
                                    max_rounds=12)
    shape = jax.tree.leaves(setup.weights0)[0].shape
    gc.collect()
    gc.disable()
    try:
        server.start()
        loop.run(max_events=100_000)
        alive = sum(1 for a in jax.live_arrays() if a.shape == shape)
    finally:
        gc.enable()
    assert server.version >= 10
    assert alive <= 8, alive
