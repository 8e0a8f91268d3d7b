"""Flat-buffer aggregation, the federation's one merge path: pack/unpack
round trips, numeric parity of the fused flat merge against the per-leaf
oracles `ref.reference_weighted_mean` and `ref.reference_mix_into`, the
fused Pallas kernel (interpret mode), the delta-accumulate variant, the
rewired server/fl_round call sites, and the merge owners' construction-
time rejection of weights that do not pack and of unknown aggregators."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregation as agg
from repro.core import federated, flatbuf
from repro.kernels import fedavg_agg, ref


def _ragged_tree(seed, dtype=jnp.float32):
    """Ragged leaf shapes, total size NOT a multiple of 128."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {
        "w1": jax.random.normal(ks[0], (7, 13)).astype(dtype),
        "b1": jax.random.normal(ks[1], (13,)).astype(dtype),
        "deep": {"w2": jax.random.normal(ks[2], (3, 5, 2)).astype(dtype),
                 "scalar": jax.random.normal(ks[3], ()).astype(dtype)},
    }


def _max_err(a, b):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------- pack / unpack ----------------

def test_pack_unpack_roundtrip_identity():
    t = _ragged_tree(0)
    b = flatbuf.bundle_for(t)
    assert b.n_params == 7 * 13 + 13 + 3 * 5 * 2 + 1
    assert b.padded_size % flatbuf.BLOCK == 0
    rt = b.unpack(b.pack(t))
    assert jax.tree.structure(rt) == jax.tree.structure(t)
    assert _max_err(t, rt) == 0.0


def test_pack_unpack_preserves_dtypes():
    t = {"f32": jnp.ones((5,), jnp.float32),
         "bf16": jnp.ones((130,), jnp.bfloat16)}
    b = flatbuf.bundle_for(t)
    rt = b.unpack(b.pack(t))
    assert rt["f32"].dtype == jnp.float32
    assert rt["bf16"].dtype == jnp.bfloat16


def test_pack_pads_with_zeros():
    t = _ragged_tree(1)
    b = flatbuf.bundle_for(t)
    flat = b.pack(t)
    assert flat.shape == (b.padded_size,)
    assert bool(jnp.all(flat[b.n_params:] == 0.0))


def test_bundle_cache_hit():
    assert flatbuf.bundle_for(_ragged_tree(2)) is \
        flatbuf.bundle_for(_ragged_tree(3))


# ---------------- fused flat vs per-leaf reference ----------------

@pytest.mark.parametrize("W", [1, 2, 8])
def test_flat_weighted_mean_matches_reference(W):
    trees = [_ragged_tree(i) for i in range(W)]
    ws = [0.5 + i for i in range(W)]
    b = flatbuf.bundle_for(trees[0])
    flat = b.unpack(flatbuf.fused_weighted_sum(
        b.pack_many(trees), flatbuf.normalized_weights(ws)))
    tree_ref = ref.reference_weighted_mean(trees, ws)
    assert _max_err(flat, tree_ref) < 1e-5


@pytest.mark.parametrize("alpha", [1.0, 0.6, 0.1])
def test_server_state_merge_matches_mix_into(alpha):
    server = _ragged_tree(10)
    trees = [_ragged_tree(i) for i in range(3)]
    ws = [1.0, 0.25, 2.0]
    st = flatbuf.FlatServerState(server)
    out = st.merge(server, trees, ws, alpha=alpha)
    expect = ref.reference_mix_into(
        server, ref.reference_weighted_mean(trees, ws), alpha)
    assert _max_err(out, expect) < 1e-5


def test_server_state_merge_repeated_rounds_reuse_mirror():
    """Round r+1 merges from round r's cached packed server buffer."""
    server = _ragged_tree(20)
    st = flatbuf.FlatServerState(server)
    expect = server
    for r in range(4):
        trees = [_ragged_tree(100 + 10 * r + i) for i in range(2 + r % 2)]
        ws = [1.0] * len(trees)
        server = st.merge(server, trees, ws, alpha=0.5)
        expect = ref.reference_mix_into(
            expect, ref.reference_weighted_mean(trees, ws), 0.5)
    assert _max_err(server, expect) < 1e-5


def test_merge_rejects_zero_weights():
    t = _ragged_tree(0)
    with pytest.raises(ValueError):
        flatbuf.FlatServerState(t).merge(t, [t], [0.0])
    vec = flatbuf.bundle_for(t).pack(t)
    with pytest.raises(ValueError):
        flatbuf.FlatServerState(t).merge_rows(t, [vec, vec], [0.0, 0.0])


def test_stale_rows_cannot_poison_later_merges():
    """A non-finite value from a past round must not leak into a later
    merge that uses fewer workers (0 * inf would be NaN)."""
    t = {"a": jnp.ones((300,))}
    st = flatbuf.FlatServerState(t)
    bad = {"a": jnp.full((300,), jnp.inf)}
    merged = st.merge(t, [t, bad], [1.0, 1.0])           # rows poisoned
    out = st.merge(merged, [{"a": jnp.full((300,), 2.0)}], [1.0], alpha=0.5)
    # reference: the mix oracle (merged=inf...) would also be inf at alpha<1 with a
    # non-finite server — so check the stale ROW specifically, alpha>=1:
    out = st.merge(out, [{"a": jnp.full((300,), 3.0)}], [1.0], alpha=1.0)
    assert bool(jnp.all(jnp.isfinite(out["a"])))
    assert bool(jnp.all(out["a"] == 3.0))


def test_alpha_one_ignores_nonfinite_server():
    """alpha>=1 is replace-on-aggregate: like the mix oracle's short-circuit, the
    server buffer must not be read (0 * inf = NaN otherwise)."""
    t = {"a": jnp.ones((300,))}
    st = flatbuf.FlatServerState(t)
    diverged = {"a": jnp.full((300,), jnp.inf)}
    bad_server = st.merge(t, [t, diverged], [1.0, 1.0])  # server now inf
    out = st.merge(bad_server, [{"a": jnp.full((300,), 2.0)}], [1.0])
    assert bool(jnp.all(out["a"] == 2.0))


def test_apply_delta_matches_treemap():
    cur, new, base = _ragged_tree(1), _ragged_tree(2), _ragged_tree(3)
    st = flatbuf.FlatServerState(cur)
    out = st.apply_delta(cur, new, base)
    expect = jax.tree.map(lambda c, n, b: c + (n - b), cur, new, base)
    assert _max_err(out, expect) < 1e-5


# ---------------- the fused Pallas kernel itself (interpret mode) --------

@pytest.mark.parametrize("W,N", [(1, 100), (2, 513), (8, 1024), (5, 777)])
def test_mix_kernel_matches_reference(W, N):
    x = jax.random.normal(jax.random.PRNGKey(0), (W, N))
    s = jax.random.normal(jax.random.PRNGKey(1), (N,))
    w = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(2), (W,)))
    alpha = 0.35
    out = fedavg_agg.fedavg_mix_flat(x, alpha * w, s, 1.0 - alpha,
                                     interpret=True)
    expect = (1 - alpha) * s + jnp.einsum("wn,w->n", x, alpha * w)
    assert float(jnp.max(jnp.abs(out - expect))) < 1e-5


@pytest.mark.parametrize("W,N", [(2, 512), (4, 333)])
def test_delta_kernel_matches_reference(W, N):
    d = jax.random.normal(jax.random.PRNGKey(3), (W, N))
    s = jax.random.normal(jax.random.PRNGKey(4), (N,))
    w = jnp.full((W,), 1.0 / W)
    out = fedavg_agg.fedavg_delta_flat(s, d, w, interpret=True)
    expect = s + jnp.einsum("wn,w->n", d, w)
    assert float(jnp.max(jnp.abs(out - expect))) < 1e-5


def test_flat_pallas_path_matches_xla_path():
    server = _ragged_tree(30)
    trees = [_ragged_tree(i) for i in range(4)]
    ws = [1.0, 2.0, 0.5, 0.25]
    out_p = flatbuf.FlatServerState(server, use_pallas=True).merge(
        server, trees, ws, alpha=0.7)
    out_x = flatbuf.FlatServerState(server, use_pallas=False).merge(
        server, trees, ws, alpha=0.7)
    assert _max_err(out_p, out_x) < 1e-5


# ---------------- rewired call sites ----------------

def test_aggregators_wrapper_still_pytree_api():
    """Every registered aggregator's scalar weights, merged by the packed
    path at alpha 1, == the per-leaf oracle with the same weights."""
    trees = [_ragged_tree(i) for i in range(3)]
    ups = [agg.WorkerUpdate(weights=t, staleness=i, n_data=1 + i)
           for i, t in enumerate(trees)]
    for name in agg.UPDATE_WEIGHT_FNS:
        ws = agg.update_weights(name, ups)
        out = flatbuf.FlatServerState(trees[0]).merge(
            trees[0], trees, ws, alpha=1.0)
        assert jax.tree.structure(out) == jax.tree.structure(trees[0])
        assert _max_err(out, ref.reference_weighted_mean(trees, ws)) < 1e-5


def test_fl_round_flat_matches_per_leaf_einsum():
    n_pods = 4
    tree = {"a": jax.random.normal(jax.random.PRNGKey(0), (n_pods, 7, 13)),
            "b": jax.random.normal(jax.random.PRNGKey(1), (n_pods, 33))}
    w = jnp.asarray([1.0, 0.0, 2.0, 0.5])
    out = jax.jit(federated.fl_round)(tree, w)
    wn = w / w.sum()
    for key in tree:
        expect = jnp.einsum("p...,p->...", tree[key], wn)
        assert float(jnp.max(jnp.abs(out[key][0] - expect))) < 1e-5
        # re-broadcast over the pod dim
        assert bool(jnp.all(out[key][0] == out[key][-1]))


def test_fl_round_delta_compressed_identity_compressor():
    n_pods = 2
    tree = {"a": jax.random.normal(jax.random.PRNGKey(2), (n_pods, 5, 3))}
    anchor = {"a": jax.random.normal(jax.random.PRNGKey(3), (5, 3))}
    w = jnp.ones((n_pods,))
    out = federated.fl_round_delta_compressed(tree, anchor, w,
                                              compressor=lambda d: d)
    expect = federated.fl_round(tree, w)
    assert _max_err(out, expect) < 1e-5


def test_merge_rows_matches_merge():
    """merge_rows (pre-packed flat vectors from the transport decode path)
    == merge (pytree updates) for the same updates."""
    server = _ragged_tree(40)
    trees = [_ragged_tree(50 + i) for i in range(3)]
    ws = [1.0, 0.5, 2.0]
    b = flatbuf.bundle_for(server)
    out_t = flatbuf.FlatServerState(server).merge(server, trees, ws, 0.6)
    out_v = flatbuf.FlatServerState(server).merge_rows(
        server, [b.pack(t) for t in trees], ws, 0.6)
    assert _max_err(out_t, out_v) == 0.0


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("alpha", [1.0, 0.5])
def test_merge_rows_matches_float64_at_f32_tolerance(use_pallas, alpha):
    """The fused merge keeps f32 accuracy: against float64 arithmetic on
    the exact f32 coefficients it hands the contraction, every element
    stays within an f32 rounding bound of the weighted sum (an operand
    rounded to bf16 would miss it by a factor of ~2^16)."""
    W = 16
    server = _ragged_tree(60)
    b = flatbuf.bundle_for(server)
    vecs = [b.pack(_ragged_tree(70 + i)) for i in range(W)]
    ws = [1.0 / (1 + i % 3) for i in range(W)]
    st = flatbuf.FlatServerState(server, use_pallas=use_pallas)
    got = np.asarray(b.pack(st.merge_rows(server, vecs, ws, alpha)),
                     np.float64)
    coef = np.zeros(W + 1, np.float32)
    coef[0] = 1.0 - alpha
    coef[1:] = alpha * flatbuf.normalized_weights(ws)
    terms = np.stack([np.asarray(b.pack(server))]
                     + [np.asarray(v) for v in vecs]).astype(np.float64)
    c = coef.astype(np.float64)[:, None]
    ref = (c * terms).sum(0)
    bound = (W + 2) * np.finfo(np.float32).eps * (np.abs(c * terms).sum(0))
    assert np.all(np.abs(got - ref) <= bound)


def test_delta_vec_matches_apply_delta():
    cur, new, base = _ragged_tree(1), _ragged_tree(2), _ragged_tree(3)
    st = flatbuf.FlatServerState(cur)
    b = st.bundle
    out_v = b.unpack(st.delta_vec(cur, b.pack(new), b.pack(base)))
    expect = flatbuf.FlatServerState(cur).apply_delta(cur, new, base)
    assert _max_err(out_v, expect) == 0.0


def test_server_aggregate_routes_through_flat(monkeypatch):
    """The server's merge lands decoded flat rows via
    FlatServerState.merge_rows, once per round."""
    from repro.core import TABLE_4_1, make_setup, run_fl

    calls = {"merge": 0}
    orig = flatbuf.FlatServerState.merge_rows

    def spy(self, *a, **k):
        calls["merge"] += 1
        return orig(self, *a, **k)

    monkeypatch.setattr(flatbuf.FlatServerState, "merge_rows", spy)
    setup = make_setup(TABLE_4_1["mnist_even"], seed=0, noise=0.2,
                       batch_size=64, het="extreme")
    h = run_fl(setup, mode="sync", selector="all", epochs_per_round=10,
               max_rounds=3)
    assert calls["merge"] == 3
    assert len(h) == 4


# ---------------- construction-time rejection ----------------

def _server(weights, aggregator="fedavg"):
    from repro.core.estimator import TimeEstimator
    from repro.core.events import EventLoop
    from repro.core.selection import make_selector
    from repro.core.server import AggregationServer
    est = TimeEstimator()
    return AggregationServer(
        weights=weights, loop=EventLoop(), estimator=est,
        selector=make_selector("all", est, 1000), eval_fn=lambda w: 0.0,
        model_bytes=1000, aggregator=aggregator)


def _topology(weights):
    from repro.core.events import EventLoop
    from repro.core.topology import Topology, TopologyConfig
    return Topology(weights=weights, loop=EventLoop(), eval_fn=lambda w: 0.0,
                    model_bytes=1000, config=TopologyConfig(n_leaves=2))


@pytest.mark.parametrize("build", [_server, _topology],
                         ids=["server", "topology"])
def test_merge_owners_reject_nonpackable_weights(build):
    """A weight tree with a non-array leaf has no merge path: the merge
    owner refuses it at construction, naming the leaf."""
    weights = {"w": jnp.ones((4,)), "tag": "not-an-array"}
    with pytest.raises(ValueError, match="'tag'"):
        build(weights)


def test_server_rejects_unknown_aggregator():
    with pytest.raises(ValueError, match="fedavg"):
        _server({"w": jnp.ones((4,))}, aggregator="median")
