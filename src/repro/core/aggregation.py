"""Aggregation weights (thesis §2.1.3, eqs 2.1–2.7).

``staleness`` of a response is ``i - xi``: current server version minus
the server version the worker fetched before training.

Every named aggregator is a scalar weight per update: the merge itself is
one fused pass over the packed ``(W, N)`` row buffer
(``flatbuf.FlatServerState``).  The per-leaf arithmetic survives only as
test oracles in ``kernels.ref``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np


@dataclass(frozen=True)
class WorkerUpdate:
    weights: object          # packed row vector, or a claimed window row
    staleness: int = 0       # i - xi
    n_data: int = 1          # batches of training data the worker used


# --- eqs 2.5-2.7: staleness weight functions -----------------------------

def linear_weight(staleness: int) -> float:          # eq 2.5
    return 1.0 / (staleness + 1.0)


def polynomial_weight(staleness: int, a: float = 0.5) -> float:   # eq 2.6
    return float((staleness + 1.0) ** (-a))


def exponential_weight(staleness: int, a: float = 0.5) -> float:  # eq 2.7
    return float(np.exp(-a * staleness))


# The aggregator registry: each name's per-update scalar weight.  "fedavg"
# is eq 2.1/2.2 (sync and async are the same formula; async simply admits
# updates with staleness > 0); the others are eqs 2.3/2.4 with WEI_x from
# a staleness weight function times the worker's data size (thesis
# §2.1.3: 'size of each worker's available data' as an extra factor).
UPDATE_WEIGHT_FNS = {
    "fedavg": lambda u: 1.0,
    "linear": lambda u: linear_weight(u.staleness) * u.n_data,
    "polynomial": lambda u: polynomial_weight(u.staleness) * u.n_data,
    "exponential": lambda u: exponential_weight(u.staleness) * u.n_data,
}


def check_aggregator(name: str, role: str = "aggregator") -> None:
    """Reject an aggregator name outside :data:`UPDATE_WEIGHT_FNS`."""
    if name not in UPDATE_WEIGHT_FNS:
        raise ValueError(f"unknown {role} {name!r}; "
                         f"have {sorted(UPDATE_WEIGHT_FNS)}")


def update_weights(aggregator: str, updates: List[WorkerUpdate]):
    """Scalar merge weight per update under ``aggregator``."""
    fn = UPDATE_WEIGHT_FNS[aggregator]
    return [fn(u) for u in updates]
