"""Sharded flat-buffer aggregation parity tier.

Pins the cross-path contract of the server-mesh substrate across three
merge paths on 1/2/4-device CPU meshes:

  * **sharded** — ``FlatServerState(mesh=agg_mesh(d))``: N-sharded rows +
    server mirror, per-shard fused merge;
  * **fused**   — the single-device flat fast path (PR 1);
  * **tree**    — the per-leaf oracles ``ref.reference_weighted_mean`` +
    ``ref.reference_mix_into``.

Reduction-order LSB tolerance (the ROADMAP "Known LSB caveat",
documented here because this tier enforces it): the flat paths reduce
over W inside one contraction while the tree reference accumulates
leaf-by-leaf update-by-update in Python order, so merges of >= 3 updates
differ in the last mantissa bits (~1e-8 per round, compounding over
rounds).  Sharding adds NOTHING on top: the packed (W, N) layout keeps
the W-reduce shard-local, so the sharded merge is asserted BIT-identical
to the fused single-device merge at every mesh size, while sharded-vs-
tree comparisons use ``TOL_TREE``.  Whole ``run_fl`` histories are
bit-identical across mesh sizes too: unpacked trees are replicated over
the mesh, so worker training and evaluation never run partitioned.

Device counts: the default tier sees one CPU device (conftest pops
XLA_FLAGS), which activates only the d=1 cases in-process — plus ONE
subprocess test that re-runs the multi-device parity checks on a forced
4-device host platform.  ``REPRO_HOST_DEVICES=4 pytest
tests/test_agg_sharded.py`` (the CI shard) runs every case in-process.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TABLE_4_1, flatbuf, make_setup, run_fl
from repro.kernels import fedavg_agg, ref
from repro.parallel import sharding as psh

MESH_SIZES = [1, 2, 4]
TOL_TREE = 5e-6          # flat-vs-tree reduction-order drift per merge

SETUP_KW = dict(seed=0, noise=0.25, batch_size=32, het="strong")


def _mesh(d: int):
    if jax.device_count() < d:
        pytest.skip(f"needs {d} devices — run with REPRO_HOST_DEVICES={d}")
    return psh.agg_mesh(d)


def _ragged_tree(seed):
    """Ragged leaves; n_params = 37*41 + 53 + 11*7*3 = 1801 — not a
    multiple of BLOCK, let alone BLOCK * mesh size (padding coverage)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"w1": jax.random.normal(ks[0], (37, 41)),
            "b": jax.random.normal(ks[1], (53,)),
            "d": {"w2": jax.random.normal(ks[2], (11, 7, 3))}}


def _max_err(a, b):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _bit_equal(a, b) -> bool:
    return all(bool(jnp.all(x == y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------- mesh-aware layout (no devices needed) ----------------

@pytest.mark.parametrize("n_shards", [1, 2, 3, 4, 7])
def test_padded_size_divisibility(n_shards):
    for n in (1, 511, 512, 513, 1801, 2**20 + 1):
        p = flatbuf.padded_size_for(n, n_shards)
        assert p >= n
        assert p % (flatbuf.BLOCK * n_shards) == 0
        assert p - n < flatbuf.BLOCK * n_shards     # minimal padding


def test_shard_spans_cover_range_exactly():
    spans = flatbuf.shard_spans(100, 1300, 512)
    # [100,512) on shard 0, [512,1024) on 1, [1024,1300) on 2
    assert spans == ((0, 100, 512, 100), (1, 0, 512, 512),
                     (2, 0, 276, 1024))
    # contiguity + exact coverage
    total = sum(hi - lo for _, lo, hi, _ in spans)
    assert total == 1200
    assert spans[0][3] == 100 and spans[-1][3] + (spans[-1][2]
                                                  - spans[-1][1]) == 1300


@pytest.mark.parametrize("d", MESH_SIZES)
def test_leaf_spans_are_mesh_aware_offsets(d):
    mesh = _mesh(d)
    t = _ragged_tree(0)
    b = flatbuf.bundle_for(t, mesh)
    assert b.padded_size % (flatbuf.BLOCK * d) == 0
    assert b.shard_size * d == b.padded_size
    vec = np.asarray(b.pack(t))
    leaves = jax.tree.leaves(t)
    for i, leaf in enumerate(leaves):
        flat = np.asarray(leaf).reshape(-1)
        got = []
        for shard, lo, hi, glo in b.leaf_spans(i):
            slo, shi = b.shard_bounds(shard)
            assert 0 <= lo < hi <= b.shard_size
            assert slo + lo == glo                  # local -> global
            got.append(vec[glo:glo + (hi - lo)])
        assert np.array_equal(np.concatenate(got), flat)
    # pack pads with zeros and unpack round-trips exactly (non-divisible N)
    assert np.all(vec[b.n_params:] == 0.0)
    assert _bit_equal(b.unpack(b.pack(t)), t)


# ---------------- sharded kernel vs XLA oracle ----------------

@pytest.mark.parametrize("d", MESH_SIZES)
def test_sharded_kernel_matches_oracle(d):
    mesh = _mesh(d)
    W, N = 5, flatbuf.BLOCK * d * 2
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    rows = jax.random.normal(ks[0], (W, N))
    srv = jax.random.normal(ks[1], (N,))
    w = jax.nn.softmax(jax.random.normal(ks[2], (W,)))
    rows_s = jax.device_put(rows, psh.agg_row_sharding(mesh))
    srv_s = jax.device_put(srv, psh.agg_vec_sharding(mesh))

    out = fedavg_agg.fedavg_mix_flat_sharded(rows_s, 0.6 * w, srv_s, 0.4,
                                             mesh=mesh, interpret=True)
    oracle = ref.reference_fedavg_sharded(rows, 0.6 * w, srv, 0.4, d)
    assert float(jnp.max(jnp.abs(out - oracle))) < 1e-5
    # the per-shard reduce IS the global reduce (layout argument)
    glob = 0.4 * srv + jnp.einsum("wn,w->n", rows, 0.6 * w)
    assert float(jnp.max(jnp.abs(oracle - glob))) < 1e-5
    # gather=True: the one collective — replicated result, same bits
    out_g = fedavg_agg.fedavg_mix_flat_sharded(rows_s, 0.6 * w, srv_s, 0.4,
                                               mesh=mesh, interpret=True,
                                               gather=True)
    assert bool(jnp.all(out_g == out))
    # no-server-term variant
    out_a = fedavg_agg.fedavg_agg_flat_sharded(rows_s, w, mesh=mesh,
                                               interpret=True)
    assert float(jnp.max(jnp.abs(
        out_a - ref.reference_fedavg(rows, w)))) < 1e-5


# ---------------- cross-path merge parity ----------------

@pytest.mark.parametrize("d", MESH_SIZES)
@pytest.mark.parametrize("alpha", [1.0, 0.6])
def test_sharded_merge_bit_identical_to_fused(d, alpha):
    """>=3-update merges over repeated rounds: the sharded path must be
    bit-identical to the fused single-device path at any mesh size."""
    mesh = _mesh(d)
    server = _ragged_tree(10)
    st_s = flatbuf.FlatServerState(server, mesh=mesh)
    st_f = flatbuf.FlatServerState(server)
    out_s, out_f = server, server
    for r in range(3):
        ups = [_ragged_tree(100 + 10 * r + i) for i in range(3 + r % 2)]
        ws = [1.0 / (1 + i % 3) for i in range(len(ups))]
        out_s = st_s.merge(out_s, ups, ws, alpha=alpha)
        out_f = st_f.merge(out_f, ups, ws, alpha=alpha)
        assert _bit_equal(out_s, out_f)


@pytest.mark.parametrize("d", MESH_SIZES)
def test_sharded_merge_matches_tree_reference(d):
    """Sharded vs per-leaf tree reference: within the documented
    reduction-order LSB tolerance for >= 3-update merges."""
    mesh = _mesh(d)
    server = _ragged_tree(20)
    st = flatbuf.FlatServerState(server, mesh=mesh)
    ups = [_ragged_tree(200 + i) for i in range(4)]
    ws = [1.0, 0.5, 2.0, 0.25]
    for alpha in (1.0, 0.6):
        out = st.merge(server, ups, ws, alpha=alpha)
        expect = ref.reference_mix_into(
            server, ref.reference_weighted_mean(ups, ws), alpha)
        assert _max_err(out, expect) < TOL_TREE


@pytest.mark.parametrize("d", MESH_SIZES)
def test_sharded_merge_rows_and_delta_vec(d):
    """The transport decode path (pre-packed shard-local vectors) merges
    bit-identically to the pytree path on the same mesh."""
    mesh = _mesh(d)
    server = _ragged_tree(30)
    ups = [_ragged_tree(300 + i) for i in range(3)]
    ws = [1.0, 0.5, 2.0]
    b = flatbuf.bundle_for(server, mesh)
    out_t = flatbuf.FlatServerState(server, mesh=mesh).merge(
        server, ups, ws, 0.6)
    out_v = flatbuf.FlatServerState(server, mesh=mesh).merge_rows(
        server, [b.pack(t) for t in ups], ws, 0.6)
    assert _bit_equal(out_t, out_v)
    # delta-accumulate in flat-vector space stays on-shard and matches
    st = flatbuf.FlatServerState(server, mesh=mesh)
    new, base = _ragged_tree(41), _ragged_tree(42)
    got = st.delta_vec(server, b.pack(new), b.pack(base))
    if d > 1:
        assert got.sharding.spec == psh.agg_vec_spec()
    expect = flatbuf.FlatServerState(server).apply_delta(server, new, base)
    assert _bit_equal(b.unpack(got), expect)


@pytest.mark.parametrize("d", MESH_SIZES)
def test_per_device_row_buffer_shrinks_linearly(d):
    mesh = _mesh(d)
    t = _ragged_tree(0)
    st = flatbuf.FlatServerState(t, mesh=mesh)
    st.merge(t, [_ragged_tree(i) for i in range(4)], [1.0] * 4, alpha=0.5)
    total = 4 * st.bundle.padded_size * 4            # (W, N) f32 bytes
    per_dev = st.row_bytes_by_device()
    assert len(per_dev) == d and set(per_dev.values()) == {total // d}
    # ... and the packed server model a mixing merge reads shards the
    # same way
    srv = {s.data.nbytes for s in st._server_buffer(t).addressable_shards}
    assert srv == {st.bundle.padded_size * 4 // d}


def _check_unpack_trains_like_one_device(mesh):
    """Unpacked trees are replicated over the server mesh, so a worker's
    local training on them runs the one-device program bit for bit (a
    leaf split along the packed axis would partition the contraction)."""
    setup = make_setup(TABLE_4_1["mnist_even"], **SETUP_KW)
    shard = next(s for s in setup.shards if len(s["x"]))
    b = flatbuf.bundle_for(setup.weights0, mesh)
    tree = b.unpack(b.pack(setup.weights0))
    assert all(leaf.sharding.is_fully_replicated
               for leaf in jax.tree.leaves(tree))
    got = setup.train_fn(tree, shard["x"], shard["y"], 20)
    expect = setup.train_fn(setup.weights0, shard["x"], shard["y"], 20)
    assert _bit_equal(got, expect)


@pytest.mark.parametrize("d", MESH_SIZES)
def test_unpacked_tree_trains_like_one_device(d):
    _check_unpack_trains_like_one_device(_mesh(d))


# ---------------- end-to-end system parity ----------------

from conftest import hist_rec as _rec   # noqa: E402


@pytest.mark.parametrize("d", MESH_SIZES)
def test_run_fl_sharded_history_parity(d):
    """Full event-driven runs at any server_mesh are bit-identical to the
    fused path: the merge is shard-local and unpacked trees are
    replicated, so training and evaluation run the one-device program."""
    _mesh(d)
    h0 = run_fl(make_setup(TABLE_4_1["mnist_even"], **SETUP_KW),
                mode="sync", selector="all", epochs_per_round=2,
                max_rounds=3)
    h1 = run_fl(make_setup(TABLE_4_1["mnist_even"], **SETUP_KW),
                mode="sync", selector="all", epochs_per_round=2,
                max_rounds=3, server_mesh=d)
    assert _rec(h1) == _rec(h0)


@pytest.mark.parametrize("d", [1, 4])
def test_run_fl_sharded_compressed_codec_parity(d):
    """server_mesh x compressed symmetric codec — the combination the
    codec-stage dispatch rule exists for (on >1-device meshes the codec
    takes the GSPMD-partitionable XLA path; Pallas stays merge-only).
    Byte counters must match the fused run exactly: the codec sees the
    same logical values whatever the sharding."""
    _mesh(d)
    kw = dict(mode="async", selector="all", async_delta=True,
              transport="topk_ef+int8", transport_frac=0.1,
              epochs_per_round=2, max_rounds=4)
    h0 = run_fl(make_setup(TABLE_4_1["mnist_even"], **SETUP_KW), **kw)
    h1 = run_fl(make_setup(TABLE_4_1["mnist_even"], **SETUP_KW),
                server_mesh=d, **kw)
    assert _rec(h1) == _rec(h0)


@pytest.mark.parametrize("d", [1, 4])
def test_run_fl_sharded_empty_round_noop(d):
    """Alg-2 time_based with T0=0 admits nobody in round 1 — the no-op
    round must behave identically on a sharded substrate."""
    _mesh(d)
    kw = dict(mode="sync", selector="time_based",
              selector_kw={"r": 2, "T0": 0.0, "A": 0.01},
              epochs_per_round=2, max_rounds=3)
    h0 = run_fl(make_setup(TABLE_4_1["mnist_even"], **SETUP_KW), **kw)
    h1 = run_fl(make_setup(TABLE_4_1["mnist_even"], **SETUP_KW),
                server_mesh=d, **kw)
    assert any(p.n_updates == 0 for p in h0[1:]), "expected a no-op round"
    assert _rec(h1) == _rec(h0)


# ---------------- multi-device coverage inside the default tier ----------

def test_multidevice_parity_subprocess():
    """The default tier runs single-device; this spawns one fresh
    interpreter on a forced 4-device host platform and re-runs the core
    parity checks there (the CI shard additionally runs the whole file
    in-process under REPRO_HOST_DEVICES=4)."""
    if jax.device_count() >= 4:
        pytest.skip("already multi-device in-process")
    # REPRO_HOST_DEVICES, not XLA_FLAGS: this module imports conftest,
    # which owns XLA_FLAGS (pops it, then re-derives it from the env var).
    # JAX_PLATFORMS=cpu: a CPU parity check, and the parent may hold the
    # one accelerator a child would otherwise wait for
    env = dict(os.environ, REPRO_HOST_DEVICES="4", JAX_PLATFORMS="cpu",
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, __file__, "--parity"],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "PARITY OK" in out.stdout


def _subprocess_parity_main():
    """Compact 2/4-device parity run for the subprocess test."""
    server = _ragged_tree(10)
    ups = [_ragged_tree(100 + i) for i in range(4)]
    ws = [1.0, 0.5, 2.0, 0.25]
    fused = flatbuf.FlatServerState(server)
    for d in (2, 4):
        mesh = psh.agg_mesh(d)
        st = flatbuf.FlatServerState(server, mesh=mesh)
        for alpha in (1.0, 0.6):
            a = st.merge(server, ups, ws, alpha=alpha)
            b = fused.merge(server, ups, ws, alpha=alpha)
            assert _bit_equal(a, b), f"d={d} alpha={alpha}"
            assert _max_err(a, ref.reference_mix_into(
                server, ref.reference_weighted_mean(ups, ws), alpha)) \
                < TOL_TREE
        per_dev = st.row_bytes_by_device()
        assert len(per_dev) == d
        assert set(per_dev.values()) == {4 * st.bundle.padded_size * 4 // d}
        _check_unpack_trains_like_one_device(mesh)
        # kernel vs oracle on the real mesh
        W, N = 3, flatbuf.BLOCK * d
        rows = jax.random.normal(jax.random.PRNGKey(d), (W, N))
        srv = jax.random.normal(jax.random.PRNGKey(d + 1), (N,))
        w = jnp.full((W,), 1.0 / W)
        out = fedavg_agg.fedavg_mix_flat_sharded(
            jax.device_put(rows, psh.agg_row_sharding(mesh)), w,
            jax.device_put(srv, psh.agg_vec_sharding(mesh)), 0.5,
            mesh=mesh, interpret=True)
        assert float(jnp.max(jnp.abs(
            out - ref.reference_fedavg_sharded(rows, w, srv, 0.5, d)))) \
            < 1e-5
    print(f"PARITY OK ({jax.device_count()} devices)")


if __name__ == "__main__":
    if "--parity" in sys.argv:
        _subprocess_parity_main()
