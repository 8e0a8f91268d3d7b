"""What decides ``correct``: the plain reference follows the federation's
first versions from the seed and is compared with what the program made.

The set-up drives the program's own federation (the loop and server the
window then continues) through its first ``check_versions`` versions,
and the benchmark records, without touching the program, what came out:
each global version (``server.weights`` as the loop leaves it), each
worker's training (the input and output of the wrapped ``train_fn``,
which shard it trained on) and each version's accuracy.  From the run it
takes only the schedule: which shards trained for which version, how many
updates each version merged, and which version each update started from.
Every number it compares against is its own, made from the seed: its own
weights, its own training (``families/<family>.py``), and here its own
merge, FedAsync mixing, top-k + int8 codec with error feedback, and
FedAdam step, in float64 NumPy.

These numbers are compared, each against its limit in
``bench/limits/<workload>.json``:

* ``train_gap``: over every compared training and by the worst leaf, the
  gap between the norms of the program's and the reference's change of
  the weights, against the larger of the reference's norm of that leaf
  and of the median leaf;
* ``version_gap``: the same for the global model's change from version 0
  to the last compared version;
* ``acc_gap``: the largest gap between the accuracy the program reported
  for a compared version (its ``eval_fn`` on the whole test set) and the
  reference's accuracy of its own version on the same images.

Each cell's limits file names the numbers it compares.

Leaves whose reference change is under a thousandth of the median leaf's
are left out of both gaps: they move by rounding alone.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

MOVE_FLOOR = 1e-3     # leaves moving less than this x the median are left out


@dataclass
class VersionRec:
    index: int
    n_updates: int
    accuracy: float
    weights: object = None


@dataclass
class TrainRec:
    at_version: int          # newest global version when the call began
    shard: int
    epochs: int
    params_in: object
    params_out: object


@dataclass
class Capture:
    """What the set-up records of the federation's first versions."""
    versions: List[VersionRec] = field(default_factory=list)
    trains: List[TrainRec] = field(default_factory=list)


# --- flat float64 views -------------------------------------------------------

def leaves(tree) -> Dict[str, np.ndarray]:
    return {k: np.asarray(v, np.float64) for k, v in sorted(tree.items())}


def flatten(tree: Dict[str, np.ndarray]) -> np.ndarray:
    return np.concatenate([np.ravel(v) for _, v in sorted(tree.items())])


def unflatten(vec: np.ndarray, like: Dict[str, np.ndarray]):
    out, o = {}, 0
    for k, v in sorted(like.items()):
        out[k] = vec[o:o + v.size].reshape(v.shape)
        o += v.size
    return out


def leaf_gap(prog_delta: Dict[str, np.ndarray],
             ref_delta: Dict[str, np.ndarray]) -> float:
    """Worst leaf's ``| |prog| - |ref| | / max(|ref|, median |ref|)``
    over the leaves that move."""
    ref_n = {k: float(np.linalg.norm(v)) for k, v in ref_delta.items()}
    med = float(np.median(list(ref_n.values())))
    worst = 0.0
    for k, rn in ref_n.items():
        if rn < MOVE_FLOOR * med:
            continue
        pn = float(np.linalg.norm(prog_delta[k]))
        worst = max(worst, abs(pn - rn) / max(rn, med))
    return worst


# --- the protocol, plainly ----------------------------------------------------

def encode_topk_int8(x: np.ndarray, frac: float):
    """Top-k by magnitude (k = max(1, floor(n * frac))), the survivors
    linearly quantised to int8 against ``max|x| / 127``: returns the
    receiver's reconstruction."""
    k = max(1, int(x.size * frac))
    thresh = max(np.sort(np.abs(x))[-k], 1e-30)
    scale = max(np.abs(x).max(), 1e-12) / 127.0
    q = np.where(np.abs(x) >= thresh,
                 np.clip(np.round(x / scale), -127, 127), 0.0)
    return q * scale


class Codec:
    """Per-worker link state of the transport: ``raw`` ships the model;
    ``topk_ef+int8`` ships, downlink, the first model whole and then the
    top-k delta against the model the worker holds, and uplink the top-k
    of (trained - fetched + residual), keeping the rest as the residual."""

    def __init__(self, name: str, frac: float):
        if name not in ("raw", "topk_ef+int8"):
            raise ValueError(f"no reference for transport {name!r}")
        self.name, self.frac = name, frac
        self.held: Dict[int, np.ndarray] = {}     # worker -> model it holds
        self.resid: Dict[int, np.ndarray] = {}    # worker -> uplink residual

    def down(self, worker: int, model: np.ndarray) -> np.ndarray:
        if self.name == "raw" or worker not in self.held:
            self.held[worker] = model
        else:
            base = self.held[worker]
            self.held[worker] = base + encode_topk_int8(model - base,
                                                        self.frac)
        return self.held[worker]

    def up(self, worker: int, trained: np.ndarray) -> np.ndarray:
        if self.name == "raw":
            return trained
        base = self.held[worker]
        x = trained - base + self.resid.get(worker, 0.0)
        recon = encode_topk_int8(x, self.frac)
        self.resid[worker] = x - recon
        return base + recon


class FedAdam:
    """``new = prev + lr * m' / (sqrt(v') + tau)`` on ``d = merged - prev``,
    no bias correction."""

    def __init__(self, beta1: float, beta2: float, lr: float, tau: float):
        self.b1, self.b2, self.lr, self.tau = beta1, beta2, lr, tau
        self.m = self.v = 0.0

    def step(self, prev: np.ndarray, merged: np.ndarray) -> np.ndarray:
        d = merged - prev
        self.m = self.b1 * self.m + (1 - self.b1) * d
        self.v = self.b2 * self.v + (1 - self.b2) * d * d
        return prev + self.lr * self.m / (np.sqrt(self.v) + self.tau)


def server_opt(run_kw: dict) -> Optional[FedAdam]:
    name = run_kw.get("server_opt")
    if name is None:
        return None
    if name != "fedadam":
        raise ValueError(f"no reference for server optimizer {name!r}")
    kw = run_kw["server_opt_kw"]
    return FedAdam(kw["beta1"], kw["beta2"], kw["lr"], kw["tau"])


def base_version(params_in, cap: Capture, upto: int) -> int:
    """The version an update started from: the newest recorded version
    the training's input equals, bit for bit (raw downlinks)."""
    for v in reversed(cap.versions[:upto + 1]):
        if v.weights is params_in:
            return v.index
    got = leaves(params_in)
    for v in reversed(cap.versions[:upto + 1]):
        w = leaves(v.weights)
        if all(np.array_equal(got[k], w[k]) for k in got):
            return v.index
    raise CheckError("a training started from no recorded version")


class CheckError(Exception):
    """The schedule the run shows is not one the reference can follow:
    the run is not correct."""


# --- the replay ---------------------------------------------------------------

def replay(cap: Capture, *, run_kw: dict, shards: List[dict],
           n_versions: int, train: Callable, accuracy: Callable,
           to_device: Callable) -> Dict[str, float]:
    """Follow the first ``n_versions`` versions with the reference and
    return the compared numbers."""
    if len(cap.versions) <= n_versions:
        raise CheckError(f"only {len(cap.versions) - 1} versions recorded, "
                         f"{n_versions} needed")
    mode = run_kw.get("mode", "sync")
    codec = Codec(run_kw.get("transport", "raw"),
                  float(run_kw.get("transport_frac", 0.1)))
    opt = server_opt(run_kw)
    like = leaves(cap.versions[0].weights)
    ref = [flatten(like)]               # the reference's versions, float64
    train_gaps, acc_gaps = [], []
    for k in range(n_versions):
        rec = cap.versions[k + 1]
        todo = [t for t in cap.trains if t.at_version == k]
        if mode == "async":
            if len(todo) != 1 or rec.n_updates != 1:
                raise CheckError(f"version {k + 1}: {len(todo)} trainings, "
                                 f"{rec.n_updates} updates merged")
            t = todo[0]
            b = base_version(t.params_in, cap, k)
            start = codec.down(t.shard, ref[b])
            out = _train(train, to_device, like, start, shards[t.shard], t)
            train_gaps.append(_train_gap(t, start, out, like))
            alpha = (float(run_kw["async_alpha"])
                     * (1.0 + k - b) ** -float(run_kw["async_stale_pow"]))
            new = (1 - alpha) * ref[k] + alpha * codec.up(t.shard, out)
        else:
            echoes = rec.n_updates - len(todo)
            if echoes < 0 or (echoes and codec.name != "raw"):
                raise CheckError(f"version {k + 1}: {len(todo)} trainings, "
                                 f"{rec.n_updates} updates merged")
            if rec.n_updates == 0:
                new = ref[k]
            else:
                rows = []
                for t in todo:
                    start = codec.down(t.shard, ref[k])
                    out = _train(train, to_device, like, start,
                                 shards[t.shard], t)
                    train_gaps.append(_train_gap(t, start, out, like))
                    rows.append(codec.up(t.shard, out))
                # workers without data answer with the model they fetched
                rows += [ref[k]] * echoes
                new = np.mean(rows, axis=0)
                if opt is not None:
                    new = opt.step(ref[k], new)
        ref.append(new)
        got = accuracy(to_device(unflatten(new, like)))
        acc_gaps.append(abs(rec.accuracy - got))
    v0 = like
    prog = leaves(cap.versions[n_versions].weights)
    refk = unflatten(ref[n_versions], like)
    return {
        "train_gap": max(train_gaps) if train_gaps else 0.0,
        "version_gap": leaf_gap({k: prog[k] - v0[k] for k in v0},
                                {k: refk[k] - v0[k] for k in v0}),
        "acc_gap": max(acc_gaps),
    }


def _train(train, to_device, like, start: np.ndarray, shard: dict,
           t: TrainRec) -> np.ndarray:
    out = train(to_device(unflatten(start, like)), shard["x"], shard["y"],
                t.epochs)
    return flatten(leaves(out))


def _train_gap(t: TrainRec, start: np.ndarray, out: np.ndarray,
               like) -> float:
    p_in, p_out = leaves(t.params_in), leaves(t.params_out)
    r = unflatten(out - start, like)
    return leaf_gap({k: p_out[k] - p_in[k] for k in p_in}, r)


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Correct when every number is there, finite and within its limit."""
    return all(numbers[k] is not None and np.isfinite(numbers[k])
               and numbers[k] <= limits[k] for k in limits)
