"""Server-optimizer parity tier (core/server_opt.py).

Three layers, mirroring tests/test_agg_sharded.py:

  * **kernel** — ``server_opt_step_flat`` (Pallas, interpret on CPU)
    against the pure-jnp oracle ``ref.reference_server_opt``; the
    shard_map'ed variant against the sliced oracle, which must agree
    EXACTLY (the step is elementwise — no cross-shard reduction at all).
  * **substrate** — the fused ``step_vec`` pass inside the FlatServerState
    merge tail against ``ref.reference_server_opt`` mapped per leaf, within the
    ROADMAP "Known LSB caveat" tolerance (the merge feeding the optimizer
    reduces in a different order on the two paths; the optimizer itself
    adds nothing — it is elementwise).
  * **system** — ``run_fl(server_opt=..., server_mesh=d)`` for
    d in {1, 2, 4}: mesh=1 bit-identical to the unsharded fused run,
    larger meshes within tolerance; optimizer state surviving
    checkpoint/resume (split == uninterrupted, float-hex), FedProx mu=0
    bit-identical to plain FedAvg, and degenerate optimizer settings
    bit-identical to ``server_opt=None``.

Multi-device cases skip unless ``REPRO_HOST_DEVICES>=d`` (the CI
``scenarios`` shard runs with 4).
"""
import math
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import hist_rec
from repro.core import flatbuf, make_setup, run_fl, server_opt as so
from repro.kernels import fedavg_agg, ref
from repro.models import mlp
from repro.parallel import sharding as psh

MESH_SIZES = [1, 2, 4]
TOL_TREE = 5e-6        # merge reduction-order drift feeding the optimizer

SETUP_KW = dict(seed=0, noise=0.25, batch_size=32, het="strong")
RUN_KW = dict(mode="sync", selector="all", epochs_per_round=3, max_rounds=4)

OPTS = [
    ("fedavgm", {"momentum": 0.9}),
    ("fedadam", {"lr": 0.05}),
    ("feddyn", {"gamma": 0.2}),
]


def _mesh(d: int):
    if jax.device_count() < d:
        pytest.skip(f"needs {d} devices — run with REPRO_HOST_DEVICES={d}")
    return psh.agg_mesh(d)


# ---------------- kernel vs oracle ----------------

@pytest.mark.parametrize("adam", [False, True])
@pytest.mark.parametrize("n", [511, 2048, 4099])
def test_opt_kernel_matches_oracle(adam, n):
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    prev, merged, m, v = (jax.random.normal(k, (n,), jnp.float32) for k in ks)
    v = jnp.abs(v)
    sc = (jnp.asarray([0.9, 0.99, 0.05, 1e-3, 0.0, 0.0], jnp.float32)
          if adam else jnp.asarray([0.9, 1.0, 0.0, 1.0], jnp.float32))
    got = fedavg_agg.server_opt_step_flat(prev, merged, m,
                                          v if adam else None, sc,
                                          adam=adam, interpret=True)
    want = ref.reference_server_opt(prev, merged, m, v if adam else None,
                                    sc, adam=adam)
    for g, w in zip(got, want):
        if w is None:
            assert g is None
            continue
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("adam", [False, True])
@pytest.mark.parametrize("d", MESH_SIZES)
def test_opt_kernel_sharded_matches_sliced_oracle(adam, d):
    mesh = _mesh(d)
    n = flatbuf.BLOCK * d * 2
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    prev, merged, m, v = (jax.random.normal(k, (n,), jnp.float32) for k in ks)
    v = jnp.abs(v)
    sc = (jnp.asarray([0.9, 0.99, 0.05, 1e-3, 0.0, 0.0], jnp.float32)
          if adam else jnp.asarray([1.0, 1.0, 1.0, 0.2], jnp.float32))
    got = fedavg_agg.server_opt_step_flat_sharded(
        prev, merged, m, v if adam else None, sc, adam=adam, mesh=mesh,
        interpret=True)
    # elementwise step, shard-local blocks: sharding must be EXACTLY the
    # unsharded kernel (no cross-shard reduction exists to reorder)
    local = fedavg_agg.server_opt_step_flat(
        prev, merged, m, v if adam else None, sc, adam=adam, interpret=True)
    for g, l in zip(got, local):
        if l is None:
            assert g is None
            continue
        assert bool(jnp.all(jnp.asarray(g) == jnp.asarray(l)))
    # and the sliced pure-jnp oracle agrees to float tolerance (fma /
    # fusion differences only)
    want = ref.reference_server_opt_sharded(
        prev, merged, m, v if adam else None, sc, adam=adam, n_shards=d)
    for g, w in zip(got, want):
        if w is None:
            continue
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-6, atol=1e-6)


# ---------------- fused step_vec vs the per-leaf oracle ----------------

@pytest.mark.parametrize("name,kw", OPTS)
def test_step_vec_matches_step_tree(name, kw):
    """Drive the same merge sequence through a FlatServerState with the
    optimizer attached (fused packed pass) and through a per-leaf weighted
    mean + ``ref.reference_server_opt`` mapped over the leaves (state
    carried here); the installs must agree within the merge's
    reduction-order tolerance."""
    template = {"w": jax.random.normal(jax.random.PRNGKey(0), (37, 41)),
                "b": jax.random.normal(jax.random.PRNGKey(1), (53,))}
    opt_flat = so.make_server_opt(name, **kw)
    flat = flatbuf.FlatServerState(template)
    flat.server_opt = opt_flat
    server_f = template
    server_t = template
    sc = opt_flat._scalars()
    zeros = jax.tree.map(lambda l: jnp.zeros(l.shape, jnp.float32), template)
    m_t, v_t = zeros, zeros
    rng = np.random.RandomState(0)
    for step in range(4):
        ups = [jax.tree.map(
                   lambda l, s=s: l + 0.1 * jnp.asarray(
                       rng.randn(*l.shape), jnp.float32),
                   server_t) for s in range(3)]
        w = [1.0, 2.0, 1.0]
        server_f = flat.merge(server_f, ups, w, alpha=1.0)
        # per-leaf reference: plain weighted mean (alpha=1 install), then
        # the optimizer oracle on every leaf
        tot = sum(w)
        mixed = jax.tree.map(
            lambda *ls: sum(wi / tot * l.astype(jnp.float32)
                            for wi, l in zip(w, ls)).astype(ls[0].dtype),
            *ups)
        outs = jax.tree.map(
            lambda p, mg, m, v: ref.reference_server_opt(
                p, mg, m, v, sc, adam=opt_flat.adam),
            server_t, mixed, m_t, v_t)
        is_out = lambda x: isinstance(x, tuple)
        server_t = jax.tree.map(lambda o: o[0], outs, is_leaf=is_out)
        m_t = jax.tree.map(lambda o: o[1], outs, is_leaf=is_out)
        if opt_flat.adam:
            v_t = jax.tree.map(lambda o: o[2], outs, is_leaf=is_out)
        err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                        - b.astype(jnp.float32))))
                  for a, b in zip(jax.tree.leaves(server_f),
                                  jax.tree.leaves(server_t)))
        assert err < TOL_TREE, (name, step, err)


# ---------------- system runs: sharded parity ----------------

@pytest.fixture(scope="module")
def setup():
    return make_setup([1] * 4, **SETUP_KW)


@pytest.fixture(scope="module")
def fused_histories(setup):
    return {name: run_fl(setup, **RUN_KW, server_opt=name, server_opt_kw=kw)
            for name, kw in OPTS}


@pytest.mark.parametrize("name,kw", OPTS)
@pytest.mark.parametrize("d", MESH_SIZES)
def test_run_fl_sharded_parity(setup, fused_histories, name, kw, d):
    _mesh(d)
    h = run_fl(setup, **RUN_KW, server_opt=name, server_opt_kw=kw,
               server_mesh=d)
    # shard-local merge and optimizer step, replicated unpacked trees:
    # bit-identical at every mesh size
    assert hist_rec(h) == hist_rec(fused_histories[name])


# ---------------- degenerate settings == server_opt=None ----------------

DEGENERATE = [
    ("fedavgm", {"momentum": 0.0, "lr": 1.0}),
    ("fedadam", {"beta1": 0.0, "beta2": 0.0, "tau": math.inf}),
    ("feddyn", {"gamma": 0.0}),
]


@pytest.mark.parametrize("name,kw", DEGENERATE)
def test_degenerate_is_bit_identical_to_none(setup, name, kw):
    h0 = run_fl(setup, **RUN_KW)
    h1 = run_fl(setup, **RUN_KW, server_opt=name, server_opt_kw=kw)
    assert hist_rec(h1) == hist_rec(h0)


# ---------------- checkpoint: split == uninterrupted ----------------

@pytest.mark.parametrize("name,kw", OPTS)
def test_checkpoint_resume_carries_optimizer_state(setup, name, kw):
    kw_run = dict(RUN_KW, max_rounds=6, server_opt=name, server_opt_kw=kw)
    h_full = run_fl(setup, **kw_run)
    with tempfile.TemporaryDirectory() as d:
        run_fl(setup, **kw_run, checkpoint_every=2, checkpoint_dir=d,
               stop_after_checkpoints=1)
        h_res = run_fl(setup, **kw_run, checkpoint_dir=d, resume=True)
    assert hist_rec(h_res) == hist_rec(h_full)


@pytest.mark.parametrize("name,kw", [OPTS[1]])
def test_topology_checkpoint_resume_carries_optimizer_state(name, kw):
    s = make_setup([1] * 6, **SETUP_KW)
    kw_run = dict(RUN_KW, max_rounds=4, topology="1x2",
                  server_opt=name, server_opt_kw=kw)
    h_full = run_fl(s, **kw_run)
    with tempfile.TemporaryDirectory() as d:
        run_fl(s, **kw_run, checkpoint_every=1, checkpoint_dir=d,
               stop_after_checkpoints=1)
        h_res = run_fl(s, **kw_run, checkpoint_dir=d, resume=True)
    assert hist_rec(h_res) == hist_rec(h_full)


# ---------------- FedProx ----------------

def test_prox_mu_zero_is_plain_sgd_bitwise():
    params = mlp.init_mlp(jax.random.PRNGKey(0), in_dim=64)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 8, 8, 1))
    y = jax.random.randint(jax.random.PRNGKey(2), (64,), 0, 10)
    a = mlp.mlp_prox_train(params, x, y, lr=0.1, epochs=2, mu=0.0)
    b = mlp.mlp_sgd_train(params, x, y, lr=0.1, epochs=2)
    assert all(bool(jnp.all(u == v))
               for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_prox_pulls_toward_anchor():
    params = mlp.init_mlp(jax.random.PRNGKey(0), in_dim=64)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 8, 8, 1))
    y = jax.random.randint(jax.random.PRNGKey(2), (64,), 0, 10)
    dist = {}
    for mu in (0.0, 1.0, 10.0):
        out = mlp.mlp_prox_train(params, x, y, lr=0.1, epochs=3, mu=mu)
        dist[mu] = math.sqrt(sum(
            float(jnp.sum((a - b).astype(jnp.float32) ** 2))
            for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(params))))
    assert dist[1.0] < dist[0.0]
    assert dist[10.0] < dist[1.0]


def test_fedprox_mu_zero_history_is_plain_fedavg():
    kw = dict(SETUP_KW)
    s0 = make_setup([1] * 4, **kw)
    s1 = make_setup([1] * 4, **kw, fedprox_mu=0.0)
    h0 = run_fl(s0, **RUN_KW)
    h1 = run_fl(s1, **RUN_KW)
    assert hist_rec(h1) == hist_rec(h0)


def test_fedprox_small_mu_stays_close():
    s0 = make_setup([1] * 4, **SETUP_KW)
    s1 = make_setup([1] * 4, **SETUP_KW, fedprox_mu=1e-4)
    h0 = run_fl(s0, **RUN_KW)
    h1 = run_fl(s1, **RUN_KW)
    assert len(h0) == len(h1)
    for a, b in zip(h0, h1):
        assert a.time == b.time            # timing model is data-independent
        assert abs(a.accuracy - b.accuracy) < 0.05


def test_fedprox_composes_with_lossy_downlink():
    # the prox anchor is whatever the worker decodes off the downlink —
    # a compressed transport must still run and converge sanely
    s = make_setup([1] * 4, **SETUP_KW, fedprox_mu=0.01)
    h = run_fl(s, **RUN_KW, transport="topk_ef+int8", transport_frac=0.3)
    assert len(h) == RUN_KW["max_rounds"] + 1
    assert all(np.isfinite(p.accuracy) for p in h)


# ---------------- factory ----------------

def test_make_server_opt_contract():
    assert so.make_server_opt(None) is None
    o = so.make_server_opt("fedavgm", momentum=0.5)
    assert isinstance(o, so.FedAvgM) and o.momentum == 0.5
    assert so.make_server_opt(o) is o
    with pytest.raises(ValueError):
        so.make_server_opt("nope")
    with pytest.raises(ValueError):
        so.make_server_opt(o, momentum=0.1)
