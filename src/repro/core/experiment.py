"""End-to-end FL experiment harness reproducing the thesis §4 setups:
synthetic MNIST/CIFAR-class data, N workers with heterogeneous profiles,
sequential / sync-FL / async-FL runs, accuracy-over-(simulated)-time
histories.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.configs.paper_cnn import CNNConfig, FAST_MNIST_CNN, MNIST_CNN
from repro.data.synth import (federated_split, make_classification_dataset,
                              partition_split)
from repro.models import cnn
from repro.parallel import sharding as psharding

from .estimator import TimeEstimator, WorkerProfile
from .events import EventLoop
from .population import WorkerPopulation
from .selection import make_selector
from .server import AggregationServer, HistoryPoint, run_sequential
from .transport import Transport
from .worker import FLWorker

# thesis tables 4.1 (10 workers): batches allocated per worker
TABLE_4_1 = {
    "mnist_sequential": [10] + [0] * 9,
    "mnist_even": [1] * 10,
    "mnist_uneven": [1, 0, 0, 3, 0, 0, 0, 2, 2, 2],
}
# thesis table 4.2 (30 workers)
TABLE_4_2 = {
    "mnist_sequential": [30] + [0] * 29,
    "mnist_even": [1] * 30,
    "mnist_uneven": [4] + [0] * 9 + [8] + [0] * 9 + [0, 2, 2, 2, 2, 2, 2, 2, 2, 2],
}


def heterogeneous_profiles(n: int, kind: str = "mixed",
                           batches: Optional[Sequence[int]] = None,
                           seed: int = 0) -> List[WorkerProfile]:
    """Profiles mimicking the thesis' three VMs with contended CPUs:
    a third fast, a third medium, a third slow."""
    rng = np.random.RandomState(seed)
    profiles = []
    for i in range(n):
        if kind == "uniform":
            freq, prop, bw = 2.0, 1.0, 100e6
        elif kind == "extreme":
            tier = i % 3
            freq = [3.0, 1.6, 0.8][tier]
            prop = [1.0, 0.9, 0.7][tier]
            bw = [200e6, 80e6, 20e6][tier]
        elif kind == "strong":   # ~3.8x spread: sync tail waits on stragglers
            tier = i % 3
            freq = [3.0, 2.0, 1.0][tier]
            prop = [1.0, 0.9, 0.8][tier]
            bw = [200e6, 80e6, 30e6][tier]
        else:  # "mixed": the thesis' same-laptop VM contention (~2.2x spread)
            tier = i % 3
            freq = [3.0, 2.4, 1.6][tier]
            prop = [1.0, 0.95, 0.85][tier]
            bw = [200e6, 100e6, 30e6][tier]
        nb = batches[i] if batches is not None else 1
        profiles.append(WorkerProfile(worker_id=f"w{i}", cpu_freq=freq,
                                      cpu_prop=prop, bandwidth=bw,
                                      n_batches=nb))
    return profiles


@dataclass
class FLSetup:
    cfg: CNNConfig
    weights0: object
    shards: List[Dict]
    profiles: List[WorkerProfile]
    test_x: np.ndarray
    test_y: np.ndarray
    model_bytes: int
    train_fn: object
    eval_fn: object
    per_batch_server: float


def make_setup(batches_per_worker: Sequence[int], *,
               cfg: CNNConfig = FAST_MNIST_CNN, model: str = "mlp",
               het: str = "mixed", batch_size: int = 32, n_test: int = 512,
               seed: int = 0, per_batch_server: float = 0.05,
               noise: float = 0.35, mlp_lr: float = 0.1,
               partition: str = "iid",
               partition_kw: Optional[dict] = None,
               fedprox_mu: float = 0.0) -> FLSetup:
    """``partition`` picks the federated data split (``data.synth``):
    ``"iid"`` is the original global shuffle (byte-identical — golden
    runs never leave it), ``"dirichlet"`` Dirichlet label skew
    (``partition_kw={"alpha": ...}``), ``"quantity"`` per-worker quantity
    skew.  ``fedprox_mu > 0`` swaps the MLP local trainer for FedProx
    (proximal term anchored at the weights the worker actually decodes
    off the downlink); ``0.0`` is the plain SGD trainer, bit-exact."""
    total_batches = sum(batches_per_worker)
    x, y = make_classification_dataset(
        total_batches * batch_size + n_test, hw=cfg.image_hw,
        channels=cfg.channels, noise=noise, seed=seed)
    test_x, test_y = x[-n_test:], y[-n_test:]
    shards = partition_split(x[:-n_test], y[:-n_test], batches_per_worker,
                             partition=partition, batch_size=batch_size,
                             seed=seed, **(partition_kw or {}))
    if model == "cnn":
        if fedprox_mu:
            raise ValueError("fedprox_mu is only wired for model='mlp'")
        weights0 = cnn.init_cnn(jax.random.PRNGKey(seed), cfg)
        train_fn = functools.partial(cnn_train_wrapper, lr=cfg.lr)
        acc_fn = cnn.cnn_accuracy
    else:
        from repro.models import mlp as mlp_mod
        in_dim = cfg.image_hw * cfg.image_hw * cfg.channels
        weights0 = mlp_mod.init_mlp(jax.random.PRNGKey(seed), in_dim=in_dim)
        train_fn = (functools.partial(mlp_prox_train_wrapper, lr=mlp_lr,
                                      mu=fedprox_mu)
                    if fedprox_mu else
                    functools.partial(mlp_train_wrapper, lr=mlp_lr))
        acc_fn = mlp_mod.mlp_accuracy
    tx, ty = jax.numpy.asarray(test_x), jax.numpy.asarray(test_y)
    eval_fn = lambda w: float(acc_fn(w, tx, ty))
    return FLSetup(cfg=cfg, weights0=weights0, shards=shards,
                   profiles=heterogeneous_profiles(len(batches_per_worker),
                                                   het, batches_per_worker,
                                                   seed),
                   test_x=test_x, test_y=test_y,
                   model_bytes=int(sum(p.size * p.dtype.itemsize
                                       for p in jax.tree.leaves(weights0))),
                   train_fn=train_fn, eval_fn=eval_fn,
                   per_batch_server=per_batch_server)


def cnn_train_wrapper(params, x, y, epochs, lr=0.01):
    import jax.numpy as jnp
    return cnn.cnn_sgd_train(params, jnp.asarray(x), jnp.asarray(y),
                             lr=lr, epochs=int(epochs))


def mlp_train_wrapper(params, x, y, epochs, lr=0.1):
    import jax.numpy as jnp
    from repro.models import mlp as mlp_mod
    return mlp_mod.mlp_sgd_train(params, jnp.asarray(x), jnp.asarray(y),
                                 lr=lr, epochs=int(epochs))


def mlp_prox_train_wrapper(params, x, y, epochs, lr=0.1, mu=0.0):
    # FedProx local step: the ``params`` this wrapper receives are the
    # worker's decode of the downlink (the lossy tx_base reconstruction
    # when the transport compresses), so the proximal anchor is the
    # global the worker actually holds — composing with lossy downlinks
    # needs no transport-side plumbing at all
    import jax.numpy as jnp
    from repro.models import mlp as mlp_mod
    return mlp_mod.mlp_prox_train(params, jnp.asarray(x), jnp.asarray(y),
                                  lr=lr, epochs=int(epochs), mu=mu)


def run_fl(setup: FLSetup, *, mode: str = "sync", selector: str = "all",
           aggregator: str = "fedavg", epochs_per_round: int = 10,
           max_rounds: int = 60, target_accuracy: Optional[float] = None,
           selector_kw: Optional[dict] = None, server_freq: float = 3.0,
           async_alpha: float = 1.0, async_stale_pow: float = 0.0,
           async_min_updates: int = 1, async_delta: bool = False,
           async_latest_table: bool = True, transport: str = "raw",
           transport_down: Optional[str] = None,
           transport_frac: float = 0.1,
           server_mesh: Optional[int] = None,
           cohort: Optional[int] = None, cohort_seed: int = 0,
           server_opt=None, server_opt_kw: Optional[dict] = None,
           partition: Optional[str] = None,
           partition_kw: Optional[dict] = None,
           topology=None,
           topology_kw: Optional[dict] = None,
           max_events: int = 200_000,
           checkpoint_every: Optional[int] = None,
           checkpoint_dir: Optional[str] = None,
           checkpoint_keep: int = 3,
           resume: bool = False,
           stop_after_checkpoints: Optional[int] = None
           ) -> List[HistoryPoint]:
    """One end-to-end FL run; returns the server's HistoryPoint sequence.

    ``mode``/``selector``/``aggregator`` pick the thesis §2-3 machinery;
    ``transport``/``transport_down``/``transport_frac`` the wire codecs
    (see ``core.transport``).  ``server_mesh`` shards the aggregation
    substrate over that many devices (a 1-D ``agg`` mesh via
    ``parallel.sharding.agg_mesh``): the packed server model, the (W, N)
    update-row buffer and every link's flat vectors split along the
    parameter axis, and the fused merge runs per shard — per-device live
    bytes shrink ~linearly with mesh size.  Any mesh size is
    bit-identical to the default fused single-device path (``None``):
    the merge is shard-local, and the model trees that workers train and
    the server evaluates are replicated over the mesh (CPU runs need
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).

    ``topology`` turns on hierarchical multi-server federation
    (``core.topology``): ``"1xL"`` / an int picks one root over ``L``
    leaf servers, each driving a disjoint worker pool (round-robin split
    of the setup's workers, or explicit ``pools`` in ``topology_kw``) and
    pushing codec'd flat-buffer deltas up a server<->server link to the
    root's fused re-merge; the returned history is the ROOT's (global
    model accuracy over time, byte counters = the server<->server
    payloads).  ``topology_kw`` overrides :class:`TopologyConfig` fields
    (``push`` sync/async, ``push_every``, ``server_codec``,
    ``server_bandwidth``, ``root_alpha``...).  ``topology="1x1"`` is the
    passthrough identity: the root is colocated with its only leaf and
    the run is bit-identical to the single-server path (pinned by the
    ``*_flat1x1`` golden aliases).  ``mode``/``max_rounds``/selection
    apply per leaf; ``target_accuracy`` is checked on the global model.

    ``cohort`` turns on massive-scale cohort sampling: each round draws
    that many alive workers (seeded by ``cohort_seed``) and only cohort
    members get links, tickets, or events — per-round cost, resident
    link state and the merge row window all scale with the cohort, not
    the population.  ``cohort >= W`` (or ``None``) is bit-identical to
    the full-population run (pinned in tests/test_scale.py).  Every run
    binds a :class:`WorkerPopulation`, so selection prices eq 3.4 over
    ``(W,)`` lane vectors in one fused pass either way.

    ``server_opt`` names a server-side optimizer (``core.server_opt``:
    ``"fedavgm"`` server momentum, ``"fedadam"`` per-coordinate adaptive
    step, ``"feddyn"`` drift correction; ``server_opt_kw`` its
    constructor kwargs, e.g. ``{"momentum": 0.9}``), applied to the
    global install as one fused pass over the packed merge result —
    ``d = merged - server`` is the pseudo-gradient.  ``None`` (default)
    keeps plain FedAvg on the byte-identical golden-pinned path; under a
    ``topology`` the ROOT carries the optimizer while leaf merges stay
    FedAvg (in passthrough ``1x1`` the lone leaf carries it, preserving
    the passthrough bit-identity).  Degenerate settings (FedAvgM
    ``momentum=0, lr=1``; FedAdam ``beta1=beta2=0, tau=inf``; FedDyn
    ``gamma=0``) short-circuit to plain ``mix_into`` bit-exactly.

    ``partition`` re-partitions the setup's pooled samples across workers
    without rebuilding the setup: ``"dirichlet"`` Dirichlet label skew
    (``partition_kw={"alpha": 0.3, "seed": ...}``), ``"quantity"``
    per-worker quantity skew, ``"iid"`` the original global shuffle.
    ``None`` leaves ``setup.shards`` untouched (the golden path).
    Worker-side FedProx is a setup-level knob instead —
    ``make_setup(fedprox_mu=)`` — because the proximal anchor lives in
    the local training step, not in the aggregation.

    ``max_events`` caps the event loop's total executed events (the run
    raises rather than silently truncate the history when it is hit).
    ``checkpoint_every=k`` saves a crash-consistent
    :class:`~repro.checkpoint.FederationSnapshot` to ``checkpoint_dir``
    every time the server version crosses a multiple of ``k``;
    ``resume=True`` restores the newest readable snapshot from
    ``checkpoint_dir`` into the freshly built federation and continues —
    bit-identically to the uninterrupted run on loss-free links.
    ``stop_after_checkpoints`` aborts right after that many saves (test
    harness for the kill-at-checkpoint/resume split).
    """
    if partition is not None:
        setup = repartition_setup(setup, partition=partition,
                                  **(partition_kw or {}))
    if topology is not None:
        from .topology import parse_topology, run_fl_topology
        res = run_fl_topology(
            setup, topology=parse_topology(topology, **(topology_kw or {})),
            mode=mode, selector=selector, aggregator=aggregator,
            epochs_per_round=epochs_per_round, max_rounds=max_rounds,
            target_accuracy=target_accuracy, selector_kw=selector_kw,
            server_freq=server_freq, async_alpha=async_alpha,
            async_stale_pow=async_stale_pow,
            async_min_updates=async_min_updates, async_delta=async_delta,
            async_latest_table=async_latest_table, transport=transport,
            transport_down=transport_down, transport_frac=transport_frac,
            server_mesh=server_mesh, cohort=cohort, cohort_seed=cohort_seed,
            server_opt=server_opt, server_opt_kw=server_opt_kw,
            max_events=max_events, checkpoint_every=checkpoint_every,
            checkpoint_dir=checkpoint_dir, checkpoint_keep=checkpoint_keep,
            resume=resume, stop_after_checkpoints=stop_after_checkpoints)
        return res.root_history
    loop, server = build_experiment(
        setup, mode=mode, selector=selector, aggregator=aggregator,
        epochs_per_round=epochs_per_round, max_rounds=max_rounds,
        target_accuracy=target_accuracy, selector_kw=selector_kw,
        server_freq=server_freq, async_alpha=async_alpha,
        async_stale_pow=async_stale_pow,
        async_min_updates=async_min_updates, async_delta=async_delta,
        async_latest_table=async_latest_table, transport=transport,
        transport_down=transport_down, transport_frac=transport_frac,
        server_mesh=server_mesh, cohort=cohort, cohort_seed=cohort_seed,
        server_opt=server_opt, server_opt_kw=server_opt_kw)
    if resume or checkpoint_every is not None:
        from repro.checkpoint import CheckpointManager, FederationSnapshot
        from repro.checkpoint.snapshot import drive_checkpointed
        if checkpoint_dir is None:
            raise ValueError("checkpointing needs checkpoint_dir")
        mgr = CheckpointManager(checkpoint_dir, keep=checkpoint_keep)
        if resume:
            got = mgr.restore_latest()
            if got is None:
                raise FileNotFoundError(
                    f"resume=True but no readable checkpoint in "
                    f"{checkpoint_dir}")
            got[1].restore_run(loop, server)
        else:
            server.start()
        if checkpoint_every is not None:
            drive_checkpointed(
                loop, mgr, lambda: server.version,
                lambda: FederationSnapshot.capture_run(loop, server),
                every=checkpoint_every, max_events=max_events,
                stop_after=stop_after_checkpoints)
        else:
            loop.run(max_events=max_events)
    else:
        server.start()
        loop.run(max_events=max_events)
    if loop.exhausted:
        raise RuntimeError(
            f"event loop exhausted max_events={max_events} with work "
            "still queued — the run did not complete and the history "
            "would be silently truncated; shrink the run (fewer "
            "rounds/workers) or raise max_events")
    return server.history


def build_experiment(setup: FLSetup, *, mode: str = "sync",
                     selector: str = "all", aggregator: str = "fedavg",
                     epochs_per_round: int = 10, max_rounds: int = 60,
                     target_accuracy: Optional[float] = None,
                     selector_kw: Optional[dict] = None,
                     server_freq: float = 3.0, async_alpha: float = 1.0,
                     async_stale_pow: float = 0.0,
                     async_min_updates: int = 1, async_delta: bool = False,
                     async_latest_table: bool = True,
                     transport: str = "raw",
                     transport_down: Optional[str] = None,
                     transport_frac: float = 0.1,
                     server_mesh: Optional[int] = None,
                     cohort: Optional[int] = None, cohort_seed: int = 0,
                     server_opt=None, server_opt_kw: Optional[dict] = None):
    """Build one single-server federation, wired but NOT started; returns
    ``(loop, server)``.  ``run_fl`` is ``build_experiment`` + start +
    drive; checkpoint restore needs the pre-start seam directly (a
    snapshot is restored into a freshly built, never-started federation
    constructed with the same arguments as the captured one)."""
    loop = EventLoop()
    est = TimeEstimator(server_freq=server_freq,
                        t_onebatch_server=setup.per_batch_server)
    pop = WorkerPopulation()
    est.bind_population(pop)
    mesh = None if server_mesh is None else psharding.agg_mesh(server_mesh)
    # one codec'd weight-exchange path for every transfer; the selection
    # policies price their eq-3.4 time budget from its expected wire bytes.
    # transport_down names the downlink codec: None = symmetric (the same
    # codec both ways), "raw" = PR-2-era uplink-only compression
    tr = Transport(setup.weights0, codec=transport,
                   down_codec=transport_down, frac=transport_frac,
                   raw_bytes=setup.model_bytes, mesh=mesh)
    if tr.tuner is not None:
        # auto mode: per-link choices price the estimator's measured
        # bandwidth, seeded by each profile's advertised nominal rate
        # (FogBus2 registration publishes link capability up front, so
        # the very first uplink already picks the regime's codec); the
        # measurement replaces the prior once the first round delivers.
        # Transport-wide byte estimates price the median the same way
        nominal = {p.worker_id: float(p.bandwidth) for p in setup.profiles}
        nominal_rep = (sorted(nominal.values())[len(nominal) // 2]
                       if nominal else None)

        def _bw_of(wid, _n=nominal):
            m = est.bandwidth(wid)
            return m if m is not None else _n.get(wid)

        def _rep_bw(_r=nominal_rep):
            m = est.median_bandwidth()
            return m if m is not None else _r

        tr.tuner.bind_bandwidth(_bw_of, _rep_bw)
    sel = make_selector(selector, est, tr.expected_oneway_bytes,
                        **(selector_kw or {}))
    server = AggregationServer(
        weights=setup.weights0, loop=loop, estimator=est, selector=sel,
        eval_fn=setup.eval_fn, model_bytes=setup.model_bytes,
        aggregator=aggregator, mode=mode, epochs_per_round=epochs_per_round,
        max_rounds=max_rounds, target_accuracy=target_accuracy,
        async_alpha=async_alpha, async_stale_pow=async_stale_pow,
        async_min_updates=async_min_updates, async_delta=async_delta,
        async_latest_table=async_latest_table, transport=tr, mesh=mesh,
        population=pop, cohort=cohort, cohort_seed=cohort_seed,
        server_opt=server_opt, server_opt_kw=server_opt_kw)
    for prof, shard in zip(setup.profiles, setup.shards):
        w = FLWorker(prof.worker_id, profile=prof, data=shard,
                     train_fn=setup.train_fn, loop=loop,
                     per_batch_time=setup.per_batch_server * server_freq /
                     max(prof.cpu_freq * prof.cpu_prop, 1e-9))
        server.add_worker(w)
    return loop, server


def repartition_setup(setup: FLSetup, *, partition: str,
                      seed: int = 0, **kw) -> FLSetup:
    """Re-split an existing setup's pooled training samples across the
    same workers with a named partitioner (``data.synth.PARTITIONERS``)
    — pool every shard back together, re-partition, and return a copy of
    the setup with only ``shards`` replaced (weights, profiles, test set
    and train_fn untouched, so two runs differing only in ``partition=``
    isolate the statistical-heterogeneity effect exactly)."""
    xs = [s["x"] for s in setup.shards]
    ys = [s["y"] for s in setup.shards]
    nonempty = [a for a in xs if len(a)]
    if not nonempty:
        return setup
    all_x = np.concatenate(nonempty)
    all_y = np.concatenate([a for a in ys if len(a)])
    batches = [p.n_batches if len(s["x"]) else 0
               for p, s in zip(setup.profiles, setup.shards)]
    total = sum(batches)
    batch_size = len(all_x) // max(total, 1)
    shards = partition_split(all_x, all_y, batches, partition=partition,
                             batch_size=batch_size, seed=seed, **kw)
    return dataclasses.replace(setup, shards=shards)


def run_sequential_baseline(setup: FLSetup, *, epochs_per_round: int = 10,
                            max_rounds: int = 60,
                            target_accuracy: Optional[float] = None
                            ) -> List[HistoryPoint]:
    all_x = np.concatenate([s["x"] for s in setup.shards if len(s["x"])])
    all_y = np.concatenate([s["y"] for s in setup.shards if len(s["x"])])
    n_batches = sum(p.n_batches for p in setup.profiles)
    return run_sequential(
        weights=setup.weights0, train_fn=setup.train_fn, eval_fn=setup.eval_fn,
        data={"x": all_x, "y": all_y},
        per_batch_time=setup.per_batch_server, n_batches=n_batches,
        epochs_per_round=epochs_per_round, max_rounds=max_rounds,
        target_accuracy=target_accuracy)


def time_to_accuracy(history: List[HistoryPoint], target: float) -> Optional[float]:
    """First (linearly interpolated) simulated time at which accuracy crosses
    ``target``."""
    for prev, h in zip(history, history[1:]):
        if h.accuracy >= target:
            if h.accuracy == prev.accuracy or prev.accuracy >= target:
                return prev.time if prev.accuracy >= target else h.time
            f = (target - prev.accuracy) / (h.accuracy - prev.accuracy)
            return prev.time + f * (h.time - prev.time)
    if history and history[0].accuracy >= target:
        return history[0].time
    return None
