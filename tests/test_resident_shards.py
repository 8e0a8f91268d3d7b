"""A worker keeps its training shard on the device: the first training
copies it (one ``shard_uploads``, its bytes under ``h2d_bytes``), every
later one reuses that copy (``shard_hits``), a worker without data copies
nothing, and a run is bit-identical to one whose ``train_fn`` is handed
fresh host arrays on every call."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import TABLE_4_1, make_setup, run_fl
from repro.core.estimator import WorkerProfile
from repro.core.events import EventLoop
from repro.core.experiment import build_experiment
from repro.core.worker import FLWorker
from repro.runtime import trace

ROUNDS = 3
# 10 workers, five of them holding 1-3 batches, five none (echoes)
BATCHES = TABLE_4_1["mnist_uneven"]
HOLDERS = sum(b > 0 for b in BATCHES)


def _setup(**kw):
    return make_setup(BATCHES, seed=0, n_test=64, **kw)


def _delta(before):
    after = trace.counters()
    return {k: after.get(k, 0) - before.get(k, 0)
            for k in ("h2d_bytes", "shard_uploads", "shard_hits")}


def _shard_bytes(setup):
    """What a device copy of each data-holding shard holds: f32 images
    and int32 labels."""
    return sum(s["x"].size * 4 + s["y"].size * 4 for s in setup.shards
               if len(s["x"]))


def _recording(setup):
    """The setup with a ``train_fn`` that keeps each call's ``x`` and
    ``y`` before training on them."""
    calls = []
    prog = setup.train_fn

    def fn(params, x, y, epochs):
        calls.append((x, y))
        return prog(params, x, y, epochs)
    return dataclasses.replace(setup, train_fn=fn), calls


@pytest.mark.parametrize("topology,leaves", [(None, 0), ("1x2", 2)])
def test_each_data_holder_uploads_once(topology, leaves):
    setup = _setup()
    before = trace.counters()
    run_fl(setup, mode="sync", selector="all", epochs_per_round=1,
           max_rounds=ROUNDS, topology=topology)
    got = _delta(before)
    assert got["shard_uploads"] == HOLDERS
    assert got["shard_hits"] == HOLDERS * (ROUNDS - 1)
    # besides the shards, a raw sync run copies only merge weights: 4
    # bytes a worker a round, and under a topology 4 a leaf at the root
    assert got["h2d_bytes"] - _shard_bytes(setup) == \
        4 * ROUNDS * (len(BATCHES) + leaves)


def test_a_second_stretch_adds_only_hits():
    setup = _setup()
    loop, server = build_experiment(setup, mode="sync", selector="all",
                                    epochs_per_round=1,
                                    max_rounds=2 * ROUNDS)
    before = trace.counters()
    server.start()
    loop.run(break_when=lambda: server.version >= ROUNDS)
    first = _delta(before)
    assert server.version == ROUNDS
    assert first["shard_uploads"] == HOLDERS
    assert first["h2d_bytes"] >= _shard_bytes(setup)
    mid = trace.counters()
    loop.run()
    second = _delta(mid)
    assert server.version == 2 * ROUNDS
    assert second["shard_uploads"] == 0
    assert second["shard_hits"] == HOLDERS * ROUNDS
    # no shard bytes: only the merge weights, 4 bytes a worker a round
    assert second["h2d_bytes"] == 4 * len(BATCHES) * ROUNDS


def test_train_fn_gets_the_same_device_arrays_every_call():
    setup, calls = _recording(_setup())
    run_fl(setup, mode="sync", selector="all", epochs_per_round=1,
           max_rounds=ROUNDS)
    assert len(calls) == HOLDERS * ROUNDS
    assert all(isinstance(a, jax.Array) for c in calls for a in c)
    distinct = []
    for x, y in calls:
        same = [d for d in distinct if d[0] is x]
        if same:
            assert same[0][1] is y
        else:
            distinct.append((x, y))
    assert len(distinct) == HOLDERS
    host = sorted((s["x"].tobytes(), s["y"].tobytes()) for s in
                  setup.shards if len(s["x"]))
    dev = sorted((np.asarray(x).tobytes(), np.asarray(y).tobytes())
                 for x, y in distinct)
    assert dev == host


def test_an_echo_worker_uploads_nothing_and_echoes():
    def no_training(*a):
        raise AssertionError("a worker without data trained")
    w = FLWorker("w0", profile=WorkerProfile("w0"),
                 data={"x": np.zeros((0, 4), np.float32),
                       "y": np.zeros((0,), np.int32)},
                 train_fn=no_training, loop=EventLoop())
    weights = {"w": jax.numpy.ones(4)}
    before = trace.counters()
    for version in range(3):
        assert w._train(weights, 1, version) is weights
    assert _delta(before) == {"h2d_bytes": 0, "shard_uploads": 0,
                              "shard_hits": 0}


def _record(hist):
    return [(p.time.hex(), p.version, float(p.accuracy).hex(), p.n_updates,
             p.up_bytes, p.down_bytes) for p in hist]


@pytest.mark.parametrize("model,kw,mode", [
    ("mlp", {}, "sync"), ("mlp", {}, "async"),
    ("mlp", {"fedprox_mu": 0.1}, "sync"), ("cnn", {}, "sync")])
def test_bit_identical_to_fresh_host_arrays(model, kw, mode):
    """The same run, once as it is and once with ``train_fn`` handed a
    fresh host copy of the shard on every call (each copied to the
    device again, as before a worker kept its shard there)."""
    setup = _setup(model=model, **kw)
    prog = setup.train_fn

    def from_host(params, x, y, epochs):
        return prog(params, np.array(x), np.array(y), epochs)
    fresh = dataclasses.replace(setup, train_fn=from_host)
    run_kw = dict(mode=mode, selector="all", epochs_per_round=2,
                  max_rounds=ROUNDS)
    finals = []
    hists = []
    for s in (setup, fresh):
        loop, server = build_experiment(s, **run_kw)
        server.start()
        loop.run()
        hists.append(_record(server.history))
        finals.append(server.weights)
    assert hists[0] == hists[1]
    for a, b in zip(jax.tree.leaves(finals[0]), jax.tree.leaves(finals[1])):
        assert a.dtype == b.dtype
        assert np.array_equal(np.asarray(a), np.asarray(b))
