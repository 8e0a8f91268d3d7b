"""Substrate tests: checkpoint/restart, compression oracles and wire bytes,
pod-level federated steps, optimizer, data pipeline, sharding rules."""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core import federated, transport
from repro.data import federated_split, make_classification_dataset, \
    synthetic_token_batches
from repro.kernels.ref import int8_dequantize, int8_quantize, topk_compress
from repro.models import init_params, train_step


# ---------------- checkpointing ----------------

def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    state = {"w": np.arange(5.0), "step": np.int32(7)}
    mgr.save(10, state, {"loss": 1.0})
    step, restored, meta = mgr.restore_latest()
    assert step == 10 and meta["loss"] == 1.0
    assert np.array_equal(restored["w"], state["w"])


def test_checkpoint_keep_n(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, {"x": np.zeros(1)})
    assert mgr.steps() == [3, 4]


def test_checkpoint_ignores_partial_writes(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    mgr.save(1, {"x": np.ones(3)})
    # a crashed writer leaves a tmp file; restore must not see it
    (tmp_path / "garbage.tmp").write_bytes(b"partial")
    step, state, _ = mgr.restore_latest()
    assert step == 1 and np.array_equal(state["x"], np.ones(3))


def test_checkpoint_skips_corrupt_snapshot(tmp_path):
    """A corrupt/truncated published snapshot (crash on a filesystem
    without atomic rename, partial copy) is skipped with a warning and
    restore falls back to the newest readable step."""
    mgr = CheckpointManager(str(tmp_path), keep=3)
    mgr.save(1, {"x": np.ones(2)})
    mgr.save(2, {"x": np.full(2, 2.0)})
    mgr.save(3, {"x": np.full(2, 3.0)})
    mgr._path(3).write_bytes(mgr._path(3).read_bytes()[:10])  # truncate
    with pytest.warns(UserWarning, match="step 3"):
        step, state, _ = mgr.restore_latest()
    assert step == 2 and np.array_equal(state["x"], np.full(2, 2.0))
    # every snapshot corrupt -> None, not an exception
    mgr._path(2).write_bytes(b"\x00garbage")
    mgr._path(1).write_bytes(b"")
    with pytest.warns(UserWarning):
        assert mgr.restore_latest() is None


def test_train_restart_equivalence(tmp_path):
    """Checkpoint at step k, restart, continue — identical params to an
    uninterrupted run (bitwise, same batches)."""
    cfg = get_config("yi-9b", reduced=True)
    opt = optim.adamw(1e-3)
    rng = jax.random.PRNGKey(0)
    params = init_params(rng, cfg)
    ost = opt.init(params)
    step = jax.jit(functools.partial(train_step, cfg=cfg, optimizer=opt))
    batches = [{
        "tokens": jax.random.randint(jax.random.PRNGKey(i), (2, 32), 0,
                                     cfg.vocab_size),
        "labels": jax.random.randint(jax.random.PRNGKey(100 + i), (2, 32), 0,
                                     cfg.vocab_size)} for i in range(4)]
    # uninterrupted
    p, o = params, ost
    for b in batches:
        p, o, _ = step(p, o, b)
    # interrupted at 2
    mgr = CheckpointManager(str(tmp_path))
    p2, o2 = params, ost
    for b in batches[:2]:
        p2, o2, _ = step(p2, o2, b)
    mgr.save(2, {"params": p2, "opt": o2})
    _, st, _ = mgr.restore_latest()
    p3 = jax.tree.map(jnp.asarray, st["params"])
    o3 = jax.tree.map(jnp.asarray, st["opt"])
    for b in batches[2:]:
        p3, o3, _ = step(p3, o3, b)
    for a, b_ in zip(jax.tree.leaves(p), jax.tree.leaves(p3)):
        assert jnp.array_equal(a, b_), "restart diverged from straight run"


# ---------------- compression ----------------

def test_topk_keeps_fraction():
    # property-test sweep over frac lives in test_fl_properties.py
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    for frac in (0.05, 0.25, 0.9):
        kept, mask = topk_compress(x, frac)
        assert int(mask.sum()) >= int(x.size * frac) * 0.9
        # kept values are exactly x on the mask
        assert jnp.allclose(kept, x * mask)


def test_int8_quantization_bounds():
    x = jax.random.normal(jax.random.PRNGKey(1), (100,)) * 3
    q, scale = int8_quantize(x)
    err = jnp.abs(int8_dequantize(q, scale) - x)
    assert float(err.max()) <= float(scale) * 0.51


def test_compression_saves_wire_bytes():
    """A ``topk_ef+int8`` link uplink at frac 0.1 puts under a quarter of
    the raw bytes on the wire (error feedback across rounds is pinned by
    tests/test_transport.py::test_link_error_feedback_recovers_mass)."""
    base = {"g": jnp.zeros((1024,))}
    t = transport.Transport(base, codec="topk_ef+int8", down_codec="raw",
                            frac=0.1)
    link = t.link("w0")
    link.encode_down(base)
    up = link.encode_up(
        {"g": jax.random.normal(jax.random.PRNGKey(3), (1024,))})
    assert up.wire_bytes < t.raw_bytes * 0.25


# ---------------- pod-level federated steps ----------------

def test_fl_round_is_weighted_mean():
    t = {"w": jnp.stack([jnp.ones((4,)), 3 * jnp.ones((4,))])}
    out = federated.fl_round(t, jnp.array([1.0, 1.0]))
    assert jnp.allclose(out["w"][0], 2.0)
    assert jnp.allclose(out["w"][0], out["w"][1])     # re-broadcast
    out2 = federated.fl_round(t, jnp.array([1.0, 0.0]))  # selection mask
    assert jnp.allclose(out2["w"][0], 1.0)


def test_fl_local_step_matches_single_pod():
    """With identical per-pod data, every pod computes the same update, and
    it equals the plain train_step on that data."""
    cfg = get_config("musicgen-medium", reduced=True)
    opt = optim.adamw(1e-3)
    rng = jax.random.PRNGKey(0)
    params = init_params(rng, cfg)
    ost = opt.init(params)
    B, S = 2, 32
    emb = jax.random.normal(rng, (B, S, cfg.d_model), jnp.bfloat16)
    lab = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    batch1 = {"embeds": emb, "labels": lab}
    # two pods, same batch each
    batch2 = {"embeds": jnp.concatenate([emb, emb]),
              "labels": jnp.concatenate([lab, lab])}
    sp = federated.stack_for_pods(params, 2)
    so = federated.stack_for_pods(ost, 2)
    sp2, so2, m2 = federated.fl_local_step(sp, so, batch2, cfg=cfg,
                                           optimizer=opt, n_pods=2)
    p1, o1, m1 = train_step(params, ost, batch1, cfg=cfg, optimizer=opt)
    pod0 = federated.unstack_pod(sp2, 0)
    pod1 = federated.unstack_pod(sp2, 1)
    for a, b in zip(jax.tree.leaves(pod0), jax.tree.leaves(pod1)):
        assert jnp.array_equal(a, b)
    for a, b in zip(jax.tree.leaves(pod0), jax.tree.leaves(p1)):
        assert jnp.allclose(a.astype(jnp.float32), b.astype(jnp.float32),
                            atol=2e-2), "pod-local step != plain step"


def test_microbatched_grads_match_full_batch():
    """n_microbatch=2 must equal n_microbatch=1 (mean-of-grads linearity)."""
    cfg = get_config("yi-9b", reduced=True)
    opt = optim.sgd(0.1)
    rng = jax.random.PRNGKey(0)
    params = init_params(rng, cfg)
    ost = opt.init(params)
    batch = {"tokens": jax.random.randint(rng, (4, 32), 0, cfg.vocab_size),
             "labels": jax.random.randint(rng, (4, 32), 0, cfg.vocab_size)}
    p1, _, _ = train_step(params, ost, batch, cfg=cfg, optimizer=opt,
                          n_microbatch=1)
    p2, _, _ = train_step(params, ost, batch, cfg=cfg, optimizer=opt,
                          n_microbatch=2)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        d = jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
        assert float(d) < 3e-2


# ---------------- data ----------------

def test_federated_split_sizes():
    x, y = make_classification_dataset(320 + 64, hw=16, seed=0)
    shards = federated_split(x[:320], y[:320], [2, 0, 3], batch_size=64)
    assert [len(s["x"]) for s in shards] == [128, 0, 192]


def test_federated_split_disjoint():
    x, y = make_classification_dataset(256, hw=16, seed=0)
    x = x + np.arange(len(x)).reshape(-1, 1, 1, 1) * 0  # keep float
    shards = federated_split(x, y, [2, 2], batch_size=64, seed=0)
    a = shards[0]["x"].reshape(len(shards[0]["x"]), -1)
    b = shards[1]["x"].reshape(len(shards[1]["x"]), -1)
    # disjoint row sets (overwhelmingly likely distinct under the generator)
    inter = set(map(lambda r: r.tobytes(), a)) & \
        set(map(lambda r: r.tobytes(), b))
    assert not inter


def test_lm_token_stream():
    it = synthetic_token_batches(vocab=128, batch=2, seq_len=64, seed=0)
    b1 = next(it)
    assert b1["tokens"].shape == (2, 64) and b1["labels"].shape == (2, 64)
    assert b1["tokens"].max() < 128
    # next-token alignment
    assert np.array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])


# ---------------- sharding rules ----------------

def test_param_specs_divisibility():
    """Dims are sharded only when divisible by the mesh axis size."""
    from jax.sharding import PartitionSpec as P
    from repro.parallel import param_specs
    cfg = get_config("yi-9b")
    shapes = jax.eval_shape(functools.partial(init_params, cfg=cfg),
                            jax.random.PRNGKey(0))

    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.empty((16, 16))
    specs = param_specs(cfg, shapes, FakeMesh())
    flat_shapes = jax.tree.leaves(shapes)
    flat_specs = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
    for sh, sp in zip(flat_shapes, flat_specs):
        for dim, ax in zip(sh.shape, tuple(sp) + (None,) * 10):
            if ax == "model":
                assert dim % 16 == 0, (sh.shape, sp)
            if ax == "data":
                assert dim % 16 == 0, (sh.shape, sp)
