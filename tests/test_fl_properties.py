"""Property tests for the FL core (hypothesis-based).

Guarded with ``pytest.importorskip``: ``hypothesis`` is a dev-only extra
(see requirements-dev.txt) and the tier-1 suite must run without it.
"""
import jax
import jax.numpy as jnp
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st   # noqa: E402

from repro.core import aggregation as agg, flatbuf         # noqa: E402
from repro.core.selection import RandomSelector            # noqa: E402
from repro.core.estimator import WorkerProfile             # noqa: E402
from repro.kernels.ref import topk_compress                # noqa: E402


def _tree(rng, scale=1.0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(rng))
    return {"a": jax.random.normal(k1, (7, 5)) * scale,
            "b": {"c": jax.random.normal(k2, (11,)) * scale}}


@given(st.integers(0, 30))
@settings(deadline=None, max_examples=20)
def test_staleness_weights_monotone_decreasing(s):
    assert agg.linear_weight(s + 1) < agg.linear_weight(s) <= 1.0
    assert agg.polynomial_weight(s + 1) < agg.polynomial_weight(s) <= 1.0
    assert agg.exponential_weight(s + 1) < agg.exponential_weight(s) <= 1.0


@given(st.lists(st.integers(0, 10), min_size=2, max_size=6))
@settings(deadline=None, max_examples=20)
def test_weighted_fedavg_convexity(stalenesses):
    """Aggregate stays inside the convex hull of the inputs (per leaf)."""
    trees = [_tree(i) for i in range(len(stalenesses))]
    ups = [agg.WorkerUpdate(weights=t, staleness=s, n_data=1)
           for t, s in zip(trees, stalenesses)]
    out = flatbuf.FlatServerState(trees[0]).merge(
        trees[0], trees, agg.update_weights("linear", ups), alpha=1.0)
    for leaf_out, *leaf_ins in zip(jax.tree.leaves(out),
                                   *[jax.tree.leaves(t) for t in trees]):
        lo = jnp.min(jnp.stack(leaf_ins), axis=0)
        hi = jnp.max(jnp.stack(leaf_ins), axis=0)
        assert bool(jnp.all(leaf_out >= lo - 1e-5))
        assert bool(jnp.all(leaf_out <= hi + 1e-5))


@given(st.floats(0.05, 0.9))
@settings(deadline=None, max_examples=10)
def test_topk_keeps_fraction(frac):
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    kept, mask = topk_compress(x, frac)
    assert int(mask.sum()) >= int(x.size * frac) * 0.9
    # kept values are exactly x on the mask
    assert jnp.allclose(kept, x * mask)


def _profiles(freqs):
    return [WorkerProfile(f"w{i}", cpu_freq=f, cpu_prop=1.0, bandwidth=1e9,
                          n_batches=1) for i, f in enumerate(freqs)]


@given(st.integers(1, 10))
@settings(deadline=None, max_examples=10)
def test_random_selector_size(k):
    sel = RandomSelector(k=k, seed=1)
    profs = _profiles([1.0] * 10)
    assert len(sel.select(profs)) == min(k, 10)


# --- durable federation: snapshot save->restore round-trip property ---

@given(st.sampled_from(["sync", "async", "async_delta"]),
       st.integers(0, 4))
@settings(deadline=None, max_examples=6)
def test_federation_snapshot_roundtrip_exact(mode, seed):
    """capture -> pickle -> restore into a fresh identically-built
    federation -> capture again: byte counters, server version, link
    tx-base presence and EF-residual norms all survive EXACTLY (no
    tolerance — a snapshot is a bit-faithful image, not an estimate)."""
    import pickle
    import tempfile

    import numpy as np

    from repro.checkpoint import CheckpointManager, FederationSnapshot
    from repro.core import TABLE_4_1, make_setup, run_fl
    from repro.core.experiment import build_experiment

    kw = dict(selector="all", epochs_per_round=2, max_rounds=3,
              transport="topk_ef+int8", transport_frac=0.1)
    if mode == "async":
        kw.update(mode="async", async_alpha=0.9, async_latest_table=False,
                  aggregator="linear")
    elif mode == "async_delta":
        kw.update(mode="async", async_delta=True)
    else:
        kw.update(mode="sync")
    setup_kw = dict(seed=seed, noise=0.25, batch_size=32, het="strong")

    with tempfile.TemporaryDirectory() as d:
        run_fl(make_setup(TABLE_4_1["mnist_even"], **setup_kw),
               checkpoint_every=1, checkpoint_dir=d,
               stop_after_checkpoints=1, **kw)
        _, snap, _ = CheckpointManager(d).restore_latest()
    snap2 = pickle.loads(pickle.dumps(snap))
    loop, server = build_experiment(
        make_setup(TABLE_4_1["mnist_even"], **setup_kw), **kw)
    snap2.restore_run(loop, server)
    snap3 = FederationSnapshot.capture_run(loop, server)

    s, s3 = snap.state["server"], snap3.state["server"]
    assert s3["version"] == s["version"]
    assert s3["total_up"] == s["total_up"]
    assert s3["total_down"] == s["total_down"]

    def norms(img):
        return sorted(
            (wid, None if li["residual"] is None
             else float(np.linalg.norm(li["residual"])).hex())
            for wid, li in img["links"].items())

    assert norms(s3["transport"]) == norms(s["transport"])
    assert snap3.clock == snap.clock
    assert sorted((r["kind"], r["t"]) for r in snap3.events) \
        == sorted((r["kind"], r["t"]) for r in snap.events)


# ---------------- non-IID partitioner properties ----------------

import numpy as np                                          # noqa: E402

from repro.data import synth                                # noqa: E402


def _pool(n=1200, seed=0):
    # labels only matter for the partition properties; tiny images keep
    # hypothesis examples fast
    x, y = synth.make_classification_dataset(n, hw=8, seed=seed)
    return x, y


_POOL_X, _POOL_Y = _pool()


@given(st.floats(0.05, 50.0), st.integers(0, 2 ** 31 - 1),
       st.lists(st.integers(0, 4), min_size=2, max_size=8))
@settings(deadline=None, max_examples=25)
def test_dirichlet_conserves_samples_exactly(alpha, seed, batches):
    """No drop, no dup: the union of shard indices is a subset-partition
    of the pool with each worker holding EXACTLY its allocated count
    (while the pool can supply it)."""
    bs = 16
    want_total = sum(batches) * bs
    hypothesis.assume(want_total <= len(_POOL_X))
    shards = synth.dirichlet_split(_POOL_X, _POOL_Y, batches,
                                   batch_size=bs, alpha=alpha, seed=seed)
    assert len(shards) == len(batches)               # all workers covered
    for nb, s in zip(batches, shards):
        assert len(s["x"]) == nb * bs                # exact allocation
        assert len(s["y"]) == nb * bs
    total = sum(len(s["x"]) for s in shards)
    assert total == want_total


@given(st.integers(0, 2 ** 31 - 1))
@settings(deadline=None, max_examples=10)
def test_dirichlet_no_index_dup(seed):
    """Strong no-dup check on the index level: partition a pool whose
    samples are made unique by construction (index-valued feature)."""
    n = 640
    x = np.arange(n, dtype=np.float32).reshape(n, 1, 1, 1)
    y = (np.arange(n) % 10).astype(np.int32)
    shards = synth.dirichlet_split(x, y, [4] * 10, batch_size=16,
                                   alpha=0.3, seed=seed)
    ids = np.concatenate([s["x"].reshape(-1) for s in shards])
    assert len(ids) == n
    assert len(np.unique(ids)) == n                 # no drop, no dup


@given(st.integers(0, 2 ** 31 - 1))
@settings(deadline=None, max_examples=10)
def test_dirichlet_seed_determinism(seed):
    a = synth.dirichlet_split(_POOL_X, _POOL_Y, [3] * 8, batch_size=16,
                              alpha=0.5, seed=seed)
    b = synth.dirichlet_split(_POOL_X, _POOL_Y, [3] * 8, batch_size=16,
                              alpha=0.5, seed=seed)
    for sa, sb in zip(a, b):
        assert np.array_equal(sa["x"], sb["x"])
        assert np.array_equal(sa["y"], sb["y"])
    c = synth.dirichlet_split(_POOL_X, _POOL_Y, [3] * 8, batch_size=16,
                              alpha=0.5, seed=seed + 1)
    assert any(not np.array_equal(sa["y"], sc["y"]) for sa, sc in zip(a, c))


def _label_hists(shards, n_classes=10):
    return np.stack([np.bincount(s["y"], minlength=n_classes)
                     for s in shards if len(s["y"])])


def test_dirichlet_alpha_extremes():
    """alpha -> inf: per-worker label histograms approach the uniform
    mixture; alpha -> 0: each worker concentrates on ~1 class."""
    big = synth.dirichlet_split(_POOL_X, _POOL_Y, [4] * 10, batch_size=16,
                                alpha=1e4, seed=0)
    tiny = synth.dirichlet_split(_POOL_X, _POOL_Y, [4] * 10, batch_size=16,
                                 alpha=1e-3, seed=0)
    h_big, h_tiny = _label_hists(big), _label_hists(tiny)
    # top-class share: ~0.1 when uniform, ~1.0 when single-label
    share_big = (h_big.max(axis=1) / h_big.sum(axis=1)).mean()
    share_tiny = (h_tiny.max(axis=1) / h_tiny.sum(axis=1)).mean()
    assert share_big < 0.25, share_big
    # pool exhaustion steals from rich classes, so perfect 1.0 is not
    # reachable for every worker — but concentration must dominate
    assert share_tiny > 0.6, share_tiny
    assert share_tiny > share_big + 0.3


@given(st.floats(0.05, 50.0), st.integers(0, 2 ** 31 - 1))
@settings(deadline=None, max_examples=15)
def test_quantity_skew_conserves_batch_total(alpha, seed):
    batches = [2, 0, 3, 1, 0, 4]
    bs = 16
    shards = synth.quantity_skew_split(_POOL_X, _POOL_Y, batches,
                                       batch_size=bs, alpha=alpha, seed=seed)
    assert len(shards) == len(batches)
    total = sum(len(s["x"]) for s in shards)
    assert total == sum(batches) * bs               # whole-batch conserved
    for nb, s in zip(batches, shards):
        assert len(s["x"]) % bs == 0                # whole batches only
        if nb == 0:
            assert len(s["x"]) == 0                 # empty workers stay empty


def test_partition_iid_is_the_original_split():
    shards_a = synth.federated_split(_POOL_X, _POOL_Y, [3] * 8,
                                     batch_size=16, seed=7)
    shards_b = synth.partition_split(_POOL_X, _POOL_Y, [3] * 8,
                                     partition="iid", batch_size=16, seed=7)
    for sa, sb in zip(shards_a, shards_b):
        assert np.array_equal(sa["x"], sb["x"])
        assert np.array_equal(sa["y"], sb["y"])
