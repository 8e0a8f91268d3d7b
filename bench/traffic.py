"""The one general traffic generator: a traffic mix is a data file under
``bench/traffic/`` (allocation, heterogeneity, run settings), and this
module turns it and ``--seed`` into the data, the worker profiles and the
keyword arguments of ``build_experiment``.

What the data is belongs to the model: the configuration's family
(``bench/families/<family>.py``) makes ``n`` items of ``(x, y)`` from the
seed with its ``dataset``, and this module deals them out.  The split
and profile generators are the benchmark's own copies of the program's
(``data/synth.py`` ``federated_split``, and ``core/experiment.py``
``heterogeneous_profiles``), so a later change to those cannot move what
the benchmark feeds the system.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Sequence

import numpy as np

# (cpu_freq GHz, cpu_prop, bandwidth bytes/s) per tier of worker i % 3
HET_TIERS = {
    "uniform": [(2.0, 1.0, 100e6)] * 3,
    "mixed": [(3.0, 1.0, 200e6), (2.4, 0.95, 100e6), (1.6, 0.85, 30e6)],
    "strong": [(3.0, 1.0, 200e6), (2.0, 0.9, 80e6), (1.0, 0.8, 30e6)],
    "extreme": [(3.0, 1.0, 200e6), (1.6, 0.9, 80e6), (0.8, 0.7, 20e6)],
}


def seed_words(seed: int) -> np.ndarray:
    """A seed of any size as the uint32 words NumPy's generators take."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    words = []
    while True:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return np.asarray(words, np.uint32)


def allocation(traffic: dict) -> List[int]:
    """Batches held by each worker: an explicit list, or ``workers``
    workers holding ``batches_each`` batches."""
    if "batches_per_worker" in traffic:
        return [int(b) for b in traffic["batches_per_worker"]]
    return [int(traffic["batches_each"])] * int(traffic["workers"])


def split(x: np.ndarray, y: np.ndarray, batches: Sequence[int],
          batch_size: int, seed: int) -> List[dict]:
    """IID shards: a seeded permutation dealt out by each worker's
    allocation (a zero entry gives that worker no data)."""
    order = np.random.RandomState(seed_words(seed)).permutation(len(x))
    shards, ptr = [], 0
    for nb in batches:
        idx = order[ptr:ptr + nb * batch_size]
        ptr += nb * batch_size
        shards.append({"x": x[idx], "y": y[idx]})
    return shards


def profiles(batches: Sequence[int], het: str):
    """The program's ``WorkerProfile`` per worker: tier ``i % 3`` of
    ``het`` sets speed and bandwidth, the allocation its batches."""
    from repro.core.estimator import WorkerProfile
    tiers = HET_TIERS[het]
    return [WorkerProfile(worker_id=f"w{i}", cpu_freq=tiers[i % 3][0],
                          cpu_prop=tiers[i % 3][1],
                          bandwidth=tiers[i % 3][2], n_batches=nb)
            for i, nb in enumerate(batches)]


@dataclass
class Traffic:
    """What one seed of a traffic mix gives the system."""
    spec: dict
    shards: List[dict]
    test_x: np.ndarray
    test_y: np.ndarray
    profiles: list
    run_kw: dict


def generate(spec: dict, cfg: dict, seed: int, family) -> Traffic:
    """Data, profiles and run settings of ``spec`` for the model
    configuration ``cfg`` of ``family``, whose ``dataset(n, cfg, spec,
    seed)`` makes the items.  ``seed`` makes the data; the schedule
    (allocation, profiles, cohort draws) is the traffic file's, the same
    for every seed."""
    batches = allocation(spec)
    bs, n_test = int(spec["batch_size"]), int(spec["n_test"])
    x, y = family.dataset(sum(batches) * bs + n_test, cfg, spec, seed)
    shards = split(x[:-n_test], y[:-n_test], batches, bs, seed + 1)
    return Traffic(spec=spec, shards=shards,
                   test_x=x[-n_test:], test_y=y[-n_test:],
                   profiles=profiles(batches, spec["het"]),
                   run_kw=dict(spec["run"]))
