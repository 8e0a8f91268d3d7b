"""One run of one cell: build the federation from the cell's files and the
seed, warm it up through its first versions, measure a window of the
program's own ``run_fl`` path, and decide ``correct``.

Everything particular to a cell is found by name: the configuration in
``bench/configs/<config>.json`` (its ``family`` names
``bench/families/<family>.py``), the traffic mix in
``bench/traffic/<traffic>.json``, the limits of the comparison in
``bench/limits/<workload>.json`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
TRACE_SECONDS = 3.0          # the traced window, at most
TRACE_DIR = ROOT / ".bench_trace"
MAX_ROUNDS = 10 ** 9         # the federation never ends inside a window


def load_module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with its files read."""
    name: str
    chips: int
    config: dict
    family: ModuleType
    traffic: dict
    limits: Dict[str, float]
    end_to_end: List[dict]
    per_layer: List[dict]


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, overrides: Optional[dict] = None) -> Cell:
    """Read the cell ``workload`` of ``BENCHMARK.json`` and its files;
    ``overrides`` replaces keys of the configuration (``"config"``) and
    the traffic (``"traffic"``, ``"run"``), for tests at a size a CPU
    holds."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    overrides = overrides or {}
    (w,) = [w for w in spec["workloads"] if w["name"] == workload]
    (c,) = [c for c in spec["configs"] if c["name"] == w["config"]]
    config = json.loads((ROOT / c["file"]).read_text())
    config.update(overrides.get("config", {}))
    traffic = json.loads(
        (HERE / "traffic" / f"{w['traffic']}.json").read_text())
    traffic.update(overrides.get("traffic", {}))
    traffic["run"] = {**traffic["run"], **overrides.get("run", {})}
    limits = json.loads((HERE / "limits" / f"{workload}.json").read_text())
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        family=load_module(HERE / "families" / f"{config['family']}.py"),
        traffic=traffic,
        limits={k: float(v) for k, v in limits["limits"].items()},
        end_to_end=[m for m in spec["end_to_end"]
                    if applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if applies(m, workload)])


class Recorder:
    """Wraps the program's ``train_fn`` and ``eval_fn`` (a
    ``TraceAnnotation`` each, and host-clock accounting; nothing that
    waits on the device) and watches the loop after every event for new
    global versions.  While ``capture`` is set it also records what the
    reference needs (``check.Capture``)."""

    def __init__(self, train_fn: Callable, eval_fn: Callable, shards: list):
        import jax
        self._annotate = jax.profiler.TraceAnnotation
        self._train_fn, self._eval_fn = train_fn, eval_fn
        self._shards = shards
        self._shard_of = {id(s["x"]): i for i, s in enumerate(shards)}
        self.train_s = self.eval_s = 0.0
        self.trains: List[tuple] = []        # (n_images, epochs) per call
        self.evals = 0
        self.capture = None
        self.server = None
        self.times: List[float] = []         # wall time of each version
        self.updates: List[int] = []         # updates merged by each version
        self._seen = 1                       # version 0 precedes the loop

    def _shard(self, x) -> int:
        i = self._shard_of.get(id(x))
        if i is not None:
            return i
        import numpy as np
        xh = np.asarray(x)
        for i, s in enumerate(self._shards):
            if s["x"].shape == xh.shape and np.array_equal(s["x"], xh):
                return i
        raise RuntimeError("a training ran on data of no shard")

    def train(self, params, x, y, epochs):
        t0 = time.perf_counter()
        with self._annotate("train_fn"):
            out = self._train_fn(params, x, y, epochs)
        self.train_s += time.perf_counter() - t0
        self.trains.append((len(x), int(epochs)))
        if self.capture is not None:
            from .check import TrainRec
            self.capture.trains.append(TrainRec(
                at_version=len(self.server.history) - 1,
                shard=self._shard(x), epochs=int(epochs), params_in=params,
                params_out=out))
        return out

    def eval(self, weights):
        t0 = time.perf_counter()
        with self._annotate("eval_fn"):
            acc = self._eval_fn(weights)
        self.eval_s += time.perf_counter() - t0
        self.evals += 1
        return acc

    def poll(self) -> bool:
        """After each event: note new versions; True if one appeared."""
        hist = self.server.history
        n = len(hist)
        if n == self._seen:
            return False
        now = time.perf_counter()
        for i in range(self._seen, n):
            self.times.append(now)
            self.updates.append(hist[i].n_updates)
            if self.capture is not None:
                from .check import VersionRec
                self.capture.versions.append(VersionRec(
                    i, hist[i].n_updates, hist[i].accuracy,
                    self.server.weights))
        self._seen = n
        return True

    def reset_counters(self) -> None:
        self.train_s = self.eval_s = 0.0
        self.trains, self.evals = [], 0


@dataclass
class Built:
    cell: Cell
    setup: object
    loop: object
    server: object
    rec: Recorder
    traffic: object
    weights0: object


def build(cell: Cell, seed: int, plant: Optional[Callable] = None) -> Built:
    """The traffic's data and profiles, the weights, and the program's
    federation over them, wired through a ``Recorder`` but not started.
    ``plant(setup, traffic)`` may return a ``train_fn`` or an ``eval_fn``
    to put in the place of the program's (the control, the faults)."""
    import dataclasses

    import jax
    from repro.core.experiment import build_experiment

    from . import traffic as traffic_mod
    tr = traffic_mod.generate(cell.traffic, cell.config, seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             seed >> 32)
    weights0 = cell.family.init_weights(key, cell.config)
    setup = cell.family.build_setup(cell.config, tr, weights0)
    if plant is not None:
        setup = dataclasses.replace(setup, **plant(setup, tr))
    rec = Recorder(setup.train_fn, setup.eval_fn, tr.shards)
    setup = dataclasses.replace(setup, train_fn=rec.train,
                                eval_fn=rec.eval)
    run_kw = dict(tr.run_kw, max_rounds=MAX_ROUNDS)
    loop, server = build_experiment(setup, **run_kw)
    rec.server = server
    return Built(cell, setup, loop, server, rec, tr, weights0)


def precompile(b: Built) -> None:
    """Compile local training at every shard size the traffic holds, and
    evaluation, before the federation starts."""
    import jax
    train_fn = b.rec._train_fn
    epochs = int(b.traffic.run_kw.get("epochs_per_round", 10))
    seen = set()
    for s in b.traffic.shards:
        n = len(s["x"])
        if n and n not in seen:
            seen.add(n)
            jax.block_until_ready(train_fn(b.weights0, s["x"], s["y"],
                                           epochs))
    b.rec._eval_fn(b.weights0)


def warm_up(b: Built, log):
    """Drive the federation through its first versions: at least the
    traffic's ``warmup.min_versions`` and ``check_versions``, and on until
    ``warmup.quiet_versions`` versions in a row compiled nothing.  The
    first ``check_versions`` versions are recorded for the reference.
    Returns the record (``check.Capture``)."""
    from .check import Capture, VersionRec
    spec = b.cell.traffic
    n_check = int(spec["check_versions"])
    need = max(int(spec["warmup"]["min_versions"]), n_check)
    quiet = int(spec["warmup"]["quiet_versions"])
    rec = b.rec
    cap = rec.capture = Capture(versions=[VersionRec(
        0, 0, b.server.history[0].accuracy, b.weights0)])
    marks = [log.events]                 # compile events at each version

    def done() -> bool:
        if not rec.poll():
            return False
        marks.extend([log.events] * (len(rec.times) + 1 - len(marks)))
        n = len(rec.times)
        if n >= n_check:
            rec.capture = None
        return n >= max(need, quiet) and marks[-1] == marks[-1 - quiet]

    b.server.start()
    b.loop.run(break_when=done)
    rec.capture = None
    if b.server.done or b.loop.exhausted:
        raise RuntimeError("the federation ended during warm-up")
    return cap


def prepare(cell: Cell, seed: int, log,
            plant: Optional[Callable] = None) -> tuple:
    """Set-up: build the federation, compile its shapes and drive it
    through its first versions.  Returns the federation and the record of
    its compared first versions (``check.Capture``)."""
    b = build(cell, seed, plant)
    precompile(b)
    return b, warm_up(b, log)


def measure(b: Built, seconds: float, log) -> dict:
    """Continue the loop until ``seconds`` have passed and the next
    version is made.  The window opens at the last warm-up version."""
    rec = b.rec
    first = len(rec.times)
    start = rec.times[-1]
    deadline = start + seconds
    ev0 = log.events
    rec.reset_counters()
    b.loop.run(break_when=lambda: rec.poll() and time.perf_counter() >= deadline)
    if b.server.done or b.loop.exhausted:
        raise RuntimeError("the federation ended inside the window")
    return {"window_s": rec.times[-1] - start,
            "versions": len(rec.times) - first,
            "updates": sum(rec.updates[first:]),
            "updates_each": rec.updates[first:],
            "window_compiles": log.events - ev0,
            "start": start}


@dataclass
class Result:
    correct: bool
    attempted: int
    failed: int
    metrics: Dict[str, dict]
    device: dict
    checks: Dict[str, dict]
    window_compiles: int
    breakdown: Optional[dict] = None

    def line(self) -> str:
        out = {"correct": self.correct, "attempted": self.attempted,
               "failed": self.failed, "metrics": self.metrics,
               "device": self.device}
        if self.breakdown is not None:
            out["breakdown"] = self.breakdown
        out["window_compiles"] = self.window_compiles
        out["checks"] = self.checks
        return json.dumps(out)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, gate: Callable[[int], dict],
        overrides: Optional[dict] = None,
        plant: Optional[Callable] = None,
        compile_cache: bool = True) -> Result:
    """One run of the cell: set-up, window, reference check.  ``plant``
    puts something else in the place of the program's training or
    evaluation (the control, the faults of the tests; see ``build``)."""
    cell = load_cell(workload, overrides)
    device = gate(cell.chips)
    import jax

    from . import check, device as device_mod, tracing
    if compile_cache:
        from repro.runtime.compile_cache import enable_compile_cache
        print(f"bench: compilation cache {enable_compile_cache()}",
              file=sys.stderr, flush=True)
    log = device_mod.CompileLog()
    b, cap = prepare(cell, seed, log, plant)
    setup_s = b.rec.times[-1] - t_start
    print(f"bench: {workload} seed {seed}: set-up {setup_s:.3f} s, "
          f"{len(b.rec.times)} warm-up versions, {log.compiles} compiles "
          f"({log.secs:.2f} s), cache hits {log.hits}, misses {log.misses}",
          file=sys.stderr, flush=True)

    breakdown, metrics = None, {}
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        with jax.profiler.TraceAnnotation("bench_window"):
            win = measure(b, min(seconds, TRACE_SECONDS), log)
        jax.profiler.stop_trace()
    else:
        win = measure(b, seconds, log)
    device = dict(device,
                  memory_peak_bytes=device_mod.memory_peak_bytes(cell.chips))
    print(f"bench: window {win['window_s']:.3f} s, {win['versions']} "
          f"versions, {win['updates']} updates, {win['window_compiles']} "
          "compiles inside the window", file=sys.stderr, flush=True)
    if trace:
        tr = tracing.reduce(tracing.load(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        metrics = per_layer(Context(
            cell=cell, win=win, rec=b.rec, trace=tr,
            n_params=cell.family.n_params(cell.config),
            peaks=device_mod.peaks_for(device["kind"])))
        device.update(busy_s=tr.busy_s, window_s=tr.window_s)
        breakdown = tr.breakdown()
    else:
        e2e = {"updates_per_s": win["updates"] / win["window_s"],
               "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    failed = sum(bool(p.failed) for p in b.setup.profiles)
    tr_data = b.traffic
    # the program's state goes before the reference runs
    b.rec.server = None
    del b
    gc.collect()
    numbers = reference_numbers(cell, cap, tr_data)
    checks = {k: {"value": numbers[k], "limit": v}
              for k, v in cell.limits.items()}
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return Result(correct=check.judge(numbers, cell.limits),
                  attempted=win["updates"], failed=failed, metrics=metrics,
                  device=device, checks=checks,
                  window_compiles=win["window_compiles"],
                  breakdown=breakdown)


def reference_numbers(cell: Cell, cap, tr) -> Dict[str, float]:
    """The compared numbers of the recorded first versions; where the
    reference cannot follow the run's schedule, none (not correct)."""
    import jax.numpy as jnp

    from . import check
    fam = cell.family
    try:
        return check.replay(
            cap, run_kw=tr.run_kw, shards=tr.shards,
            n_versions=int(cell.traffic["check_versions"]),
            train=lambda p, x, y, e: fam.ref_train(cell.config, p, x, y, e),
            accuracy=lambda p: fam.ref_accuracy(p, tr.test_x, tr.test_y),
            to_device=lambda t: {k: jnp.asarray(v, jnp.float32)
                                 for k, v in t.items()})
    except check.CheckError as e:
        print(f"bench: the reference cannot follow the run: {e}",
              file=sys.stderr, flush=True)
        return {k: None for k in cell.limits}


def per_layer(ctx: "Context") -> Dict[str, dict]:
    """Each per-layer metric of the cell that its reader finds."""
    out = {}
    for m in ctx.cell.per_layer:
        value = load_module(HERE / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


@dataclass
class Context:
    """What a per-layer metric's reader reads: the traced window's host
    accounting, the reduced trace, the cell, and the chip's peaks."""
    cell: Cell
    win: dict
    rec: Recorder
    trace: object
    n_params: int
    peaks: Optional[dict]
