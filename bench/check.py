"""What decides ``correct``: the plain reference follows the federation's
first versions from the seed and is compared with what the program made.

The set-up drives the program's own federation (the loop and server the
window then continues) through its first ``check_versions`` versions,
and the benchmark records, without touching the program, what came out:
each version's accuracy, each worker's training (which shard it trained
on, which version its input was, and the norm by leaf of the change the
wrapped ``train_fn`` made) and the weights of version 0 and of the last
compared version (``server.weights`` as the loop leaves it).  From the
run it takes only the schedule: which shards trained for which version,
how many updates each version merged, and which version each update
started from.
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

Each cell's limits file names the numbers it compares.  Parameter trees
may nest in any way: leaves are named by their tree path.

Leaves whose reference change is under a thousandth of the median leaf's
are left out of both gaps: they move by rounding alone.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np
from jax.tree_util import keystr, tree_flatten_with_path, tree_unflatten

MOVE_FLOOR = 1e-3     # leaves moving less than this x the median are left out


@dataclass
class VersionRec:
    index: int
    n_updates: int
    accuracy: float
    weights: object = None   # kept while a comparison can still read it


@dataclass
class TrainRec:
    at_version: int          # newest global version when the call began
    shard: int
    epochs: int
    base: Optional[int]      # the kept version its input equals, if any
    norms: Dict[str, float]  # per leaf, the float64 norm of out - in


@dataclass
class Capture:
    """What the set-up records of the federation's first versions: each
    version's accuracy and merged updates, each training's schedule and
    the per-leaf norms of its change, and the weights of version 0 and of
    the last compared version (``n_check``).  While it records, the newest
    version's weights stay too, for the trainings that start from it; with
    ``stale_bases`` (async, where a training may start from any older
    version) every version's weights stay until ``close``."""
    n_check: int
    stale_bases: bool = False
    versions: List[VersionRec] = field(default_factory=list)
    trains: List[TrainRec] = field(default_factory=list)

    def add_version(self, rec: VersionRec) -> None:
        self.versions.append(rec)
        if not self.stale_bases:
            self._release(keep_newest=True)

    def add_training(self, at_version: int, shard: int, epochs: int,
                     params_in, params_out) -> None:
        """Record one training: its change's norm by leaf, read back leaf
        by leaf, and the version it started from; neither tree is kept."""
        self.trains.append(TrainRec(
            at_version, shard, epochs, self._base(params_in, at_version),
            leaf_norms(params_out, params_in)))

    def close(self) -> None:
        """The end of the record: only version 0's and the last compared
        version's weights stay."""
        self._release(keep_newest=False)

    def _release(self, keep_newest: bool) -> None:
        newest = len(self.versions) - 1
        for v in self.versions:
            if v.index not in (0, self.n_check) and not (
                    keep_newest and v.index == newest):
                v.weights = None

    def _base(self, params_in, upto: int) -> Optional[int]:
        """The newest kept version up to ``upto`` that the training's input
        is, else equals bit for bit (raw downlinks); None where none does."""
        kept = [v for v in reversed(self.versions[:upto + 1])
                if v.weights is not None]
        for v in kept:
            if v.weights is params_in:
                return v.index
        got = leaves(params_in)
        for v in kept:
            w = leaves(v.weights)
            if len(w) == len(got) and all(
                    a == b and np.array_equal(np.asarray(x), np.asarray(y))
                    for (a, x), (b, y) in zip(got, w)):
                return v.index
        return None


# --- flat float64 views, by tree path ------------------------------------------

def leaves(tree) -> List[Tuple[str, Any]]:
    """``(path, leaf)`` of every leaf of ``tree``, however nested, sorted
    by the path's ``keystr``."""
    flat, _ = tree_flatten_with_path(tree)
    return sorted(((keystr(p), v) for p, v in flat), key=lambda kv: kv[0])


def flatten(tree) -> np.ndarray:
    """The leaves of ``tree`` in ``leaves`` order as one float64 vector,
    filled one leaf at a time."""
    named = leaves(tree)
    out = np.empty(sum(int(np.size(v)) for _, v in named), np.float64)
    o = 0
    for _, v in named:
        n = int(np.size(v))
        out[o:o + n] = np.ravel(np.asarray(v))
        o += n
    return out


def unflatten(vec: np.ndarray, like):
    """``vec`` cut into views shaped as ``like``'s leaves, in ``like``'s
    nesting (``flatten``'s inverse)."""
    flat, treedef = tree_flatten_with_path(like)
    order = sorted(range(len(flat)), key=lambda i: keystr(flat[i][0]))
    parts: List[Any] = [None] * len(flat)
    o = 0
    for i in order:
        shape = np.shape(flat[i][1])
        n = int(np.prod(shape, dtype=np.int64))
        parts[i] = vec[o:o + n].reshape(shape)
        o += n
    return tree_unflatten(treedef, parts)


def leaf_norms(a, b) -> Dict[str, float]:
    """Per leaf, the float64 norm of ``a - b`` (trees of one shape)."""
    return {k: float(np.linalg.norm(np.asarray(x, np.float64)
                                    - np.asarray(y, np.float64)))
            for (k, x), (_, y) in zip(leaves(a), leaves(b))}


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float]) -> float:
    """Worst leaf's ``| |prog| - |ref| | / max(|ref|, median |ref|)`` over
    the leaves that move, from the per-leaf norms of both changes."""
    med = float(np.median(list(ref.values())))
    worst = 0.0
    for k, rn in ref.items():
        if rn < MOVE_FLOOR * med:
            continue
        worst = max(worst, abs(prog[k] - rn) / max(rn, med))
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
        if self.name == "raw":
            return model
        if worker not in self.held:
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


class CheckError(Exception):
    """The schedule the run shows is not one the reference can follow:
    the run is not correct."""


# --- the replay ---------------------------------------------------------------

def replay(cap: Capture, *, run_kw: dict, shards: List[dict],
           n_versions: int, train: Callable, accuracy: Callable,
           to_device: Callable) -> Dict[str, float]:
    """Follow the first ``n_versions`` versions with the reference and
    return the compared numbers.  The host holds, in float64, version 0,
    the current version, the sum of its rows and one trained row (with
    the codec's per-worker state and FedAdam's moments where the run has
    them); in async also the versions later trainings start from."""
    if len(cap.versions) <= n_versions:
        raise CheckError(f"only {len(cap.versions) - 1} versions recorded, "
                         f"{n_versions} needed")
    mode = run_kw.get("mode", "sync")
    codec = Codec(run_kw.get("transport", "raw"),
                  float(run_kw.get("transport_frac", 0.1)))
    opt = server_opt(run_kw)
    like = cap.versions[0].weights
    v0 = cur = flatten(like)
    ref = {0: v0}            # the reference's versions that are still read
    train_gaps, acc_gaps = [], []
    for k in range(n_versions):
        rec = cap.versions[k + 1]
        todo = [t for t in cap.trains if t.at_version == k]
        if mode == "async":
            if len(todo) != 1 or rec.n_updates != 1:
                raise CheckError(f"version {k + 1}: {len(todo)} trainings, "
                                 f"{rec.n_updates} updates merged")
            t = todo[0]
            b = t.base
            if b is None:
                raise CheckError("a training started from no recorded "
                                 "version")
            start = codec.down(t.shard, ref[b])
            out = _train(train, to_device, like, start, shards[t.shard], t)
            train_gaps.append(_train_gap(t, start, out, like))
            alpha = (float(run_kw["async_alpha"])
                     * (1.0 + k - b) ** -float(run_kw["async_stale_pow"]))
            new = (1 - alpha) * cur + alpha * codec.up(t.shard, out)
        else:
            echoes = rec.n_updates - len(todo)
            if echoes < 0 or (echoes and codec.name != "raw"):
                raise CheckError(f"version {k + 1}: {len(todo)} trainings, "
                                 f"{rec.n_updates} updates merged")
            if rec.n_updates == 0:
                new = cur
            else:
                # the rows' mean, summed in their order as np.mean sums
                new = None
                for t in todo:
                    start = codec.down(t.shard, cur)
                    out = _train(train, to_device, like, start,
                                 shards[t.shard], t)
                    train_gaps.append(_train_gap(t, start, out, like))
                    row = codec.up(t.shard, out)
                    if new is None:
                        new = row
                    else:
                        new += row
                    del start, out, row
                # workers without data answer with the model they fetched
                for _ in range(echoes):
                    if new is None:
                        new = cur.copy()
                    else:
                        new += cur
                new /= rec.n_updates
                if opt is not None:
                    new = opt.step(cur, new)
        cur = ref[k + 1] = new
        later = ({t.base for t in cap.trains if t.at_version > k}
                 if mode == "async" else ())
        ref = {i: v for i, v in ref.items() if i in (0, k + 1) or i in later}
        got = accuracy(to_device(unflatten(new, like)))
        acc_gaps.append(abs(rec.accuracy - got))
    prog = cap.versions[n_versions].weights
    return {
        "train_gap": max(train_gaps) if train_gaps else 0.0,
        "version_gap": leaf_gap(leaf_norms(prog, unflatten(v0, like)),
                                leaf_norms(unflatten(cur, like),
                                           unflatten(v0, like))),
        "acc_gap": max(acc_gaps),
    }


def _train(train, to_device, like, start: np.ndarray, shard: dict,
           t: TrainRec) -> np.ndarray:
    out = train(to_device(unflatten(start, like)), shard["x"], shard["y"],
                t.epochs)
    return flatten(out)


def _train_gap(t: TrainRec, start: np.ndarray, out: np.ndarray,
               like) -> float:
    return leaf_gap(t.norms, leaf_norms(unflatten(out, like),
                                        unflatten(start, like)))


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Correct when every number is there, finite and within its limit."""
    return all(numbers[k] is not None and np.isfinite(numbers[k])
               and numbers[k] <= limits[k] for k in limits)
