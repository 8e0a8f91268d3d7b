"""The program's own spans and counters in a traced window of one cell.

    python3 bench/spans.py --workload mnist-xdev-sync-topk --seed 7

Drives the cell's set-up and one traced window as ``bench/run.py --trace
1`` does (``harness.prepare``, ``harness.measure`` under a
``bench_window`` span), with two additions:

* ``load`` also keeps the program's host spans (``fl.*``, from
  ``repro.runtime.trace``) and the benchmark's ``train_fn``/``eval_fn``,
  each with its thread line; ``reduce`` gives each span name its self
  time (its duration less what its child spans on the same line cover)
  and its count, and cuts each idle gap of the device where host spans
  begin and end, labelling each piece by the innermost host span around
  it (``loop`` where none is);
* the window holds the difference of ``repro.runtime.trace.counters()``
  between its opening and its close, under ``win["counters"]``.

It prints one JSON line: the cell's per-layer metrics, the metrics of
``METRICS`` (readers in ``bench/metrics/``, each ``None`` where the
program has no such span or counter), the counters, self time and count
by span, idle seconds by label and the breakdown.  ``--record PATH``
also writes the plain record of the window's first ``--record-ms``
milliseconds, for a test fixture.  Runs on the chip, like ``run.py``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import tracing  # noqa: E402

PREFIX = "fl."
BENCH_SPANS = ("train_fn", "eval_fn")
# per-layer metrics read from the program's spans and counters, and units
METRICS = {"codec_host_ms_per_update": "ms",
           "sync_wait_ms_per_update": "ms",
           "merge_host_ms_per_version": "ms",
           "control_host_ms_per_update": "ms",
           "host_syncs_per_update": "syncs",
           "h2d_kb_per_update": "KB"}
TRACE_DIR = ROOT / ".bench_trace_spans"


def _newest(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(trace_dir: str) -> dict:
    """``tracing.load``'s record, plus ``spans``: ``[name, line, start ns,
    duration ns]`` of each ``fl.*``, ``train_fn`` and ``eval_fn`` event
    on the host."""
    from jax.profiler import ProfileData
    rec = tracing.load(trace_dir)
    spans = []
    for plane in ProfileData.from_file(_newest(trace_dir)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            spans.extend([e.name, f"{plane.name}#{i}", float(e.start_ns),
                          float(e.duration_ns)] for e in line.events
                         if e.name.startswith(PREFIX)
                         or e.name in BENCH_SPANS)
    rec["spans"] = spans
    return rec


@dataclasses.dataclass
class Spans(tracing.Reduced):
    """``tracing.Reduced`` whose idle gaps are cut where host spans begin
    and end, each piece labelled by the innermost span around it, and the
    self seconds and count of each span name in the window."""
    self_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    count: Dict[str, int] = dataclasses.field(default_factory=dict)

    def breakdown(self) -> dict:
        """``tracing.Reduced.breakdown``: every label's total, then the
        longest pieces, ten entries in all (more where there are more
        labels)."""
        bd = super().breakdown()
        n = max(10, len(self.idle_by_label()))
        bd["idle_gaps"] = bd["idle_gaps"][:n]
        return bd


def self_times(spans: List[tuple]) -> tuple:
    """Self seconds and count by name of ``(name, line, start, end)``
    spans, which nest on each line: a span's duration less its direct
    children's."""
    self_ns: Dict[str, float] = {}
    count: Dict[str, int] = {}
    open_: Dict[str, list] = {}          # line -> stack of [name, end]
    for name, line, s, e in sorted(spans, key=lambda x: (x[2], -x[3])):
        stack = open_.setdefault(line, [])
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            parent = stack[-1][0]
            self_ns[parent] = self_ns.get(parent, 0.0) - (e - s)
        self_ns[name] = self_ns.get(name, 0.0) + (e - s)
        count[name] = count.get(name, 0) + 1
        stack.append([name, e])
    return {n: v / 1e9 for n, v in self_ns.items()}, count


def label_gaps(gaps: List[tuple], spans: List[tuple]) -> List[tuple]:
    """Cut each of the sorted, disjoint ``(start, end)`` gaps where a span
    begins or ends, and name each piece by the innermost (latest begun)
    span around it, else ``loop``: ``(label, ns)`` pieces, from one sweep
    over the spans' sorted edges."""
    edges = sorted([(s, 1, i) for i, (_, _, s, _) in enumerate(spans)]
                   + [(e, 0, i) for i, (_, _, _, e) in enumerate(spans)])
    active: List[int] = []
    out, k = [], 0

    def advance(t):
        nonlocal k
        while k < len(edges) and edges[k][0] <= t:
            _, is_start, i = edges[k]
            if is_start:
                active.append(i)
            else:
                active.remove(i)
            k += 1

    for a, b in gaps:
        advance(a)
        t = a
        while t < b:
            nxt = min(edges[k][0] if k < len(edges) else b, b)
            out.append((spans[active[-1]][0] if active else "loop",
                        nxt - t))
            t = nxt
            advance(t)
    return out


def reduce(rec: dict) -> Spans:
    """``tracing.reduce``, with the idle gaps cut and labelled by the
    innermost host span, and each span's self time and count inside the
    window."""
    base = tracing.reduce(rec)
    (lo, hi), = [(s, s + d) for n, s, d in rec["host"]
                 if n == "bench_window"]
    spans = [(n, line, max(s, lo), min(s + d, hi))
             for n, line, s, d in rec.get("spans", ())
             if min(s + d, hi) > max(s, lo)]
    gaps: List[tuple] = []
    devices = [d for d in rec["devices"].values() if d["ops"]]
    for dev in devices:
        g = tracing.gaps(tracing.clip(dev["ops"], lo, hi), lo, hi)
        gaps += [(lab, ns / 1e9) for lab, ns in label_gaps(g, spans)]
    self_s, count = self_times(spans)
    return Spans(**{**vars(base), "gaps": gaps},
                 self_s={k: v for k, v in self_s.items()
                         if k.startswith(PREFIX)},
                 count={k: v for k, v in count.items()
                        if k.startswith(PREFIX)})


def span_ms(ctx, names, per: str) -> Optional[float]:
    """Milliseconds of self time of the spans ``names`` in the traced
    window, per ``per`` (``"updates"`` or ``"versions"``); None where the
    trace holds none of them."""
    self_s = getattr(ctx.trace, "self_s", None) or {}
    if not ctx.win[per] or not any(n in self_s for n in names):
        return None
    return 1e3 * sum(self_s.get(n, 0.0) for n in names) / ctx.win[per]


def counted(ctx, name: str) -> Optional[float]:
    """The window's count of ``name`` per update; None where the program
    keeps no such counter."""
    counters = ctx.win.get("counters")
    if counters is None or name not in counters or not ctx.win["updates"]:
        return None
    return counters[name] / ctx.win["updates"]


def cut(rec: dict, ms: float) -> dict:
    """The record of the window's first ``ms`` milliseconds (events that
    overlap it, whole), with ``bench_window`` shortened to it."""
    (lo, _), = [(s, s + d) for n, s, d in rec["host"]
                if n == "bench_window"]
    hi = lo + ms * 1e6
    keep = lambda evs: [e for e in evs if e[-2] < hi and e[-2] + e[-1] > lo]
    return {"devices": {k: {kk: keep(v) for kk, v in d.items()}
                        for k, d in rec["devices"].items()},
            "host": keep([e for e in rec["host"] if e[0] != "bench_window"])
            + [["bench_window", lo, hi - lo]],
            "spans": keep(rec["spans"])}


def _counters():
    """``repro.runtime.trace.counters`` where the program has it."""
    try:
        from repro.runtime.trace import counters
    except ImportError:
        return None
    return counters


def traced_window(b, seconds: float, log, trace_dir: Path):
    """One window of ``seconds`` under the profiler: the harness's
    window record with the counters' difference, and the trace's
    record."""
    import jax

    from bench import harness
    counters = _counters()
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    c0 = counters() if counters else None
    with jax.profiler.TraceAnnotation("bench_window"):
        win = harness.measure(b, seconds, log)
    if counters:
        c1 = counters()
        win["counters"] = {k: v - c0.get(k, 0) for k, v in c1.items()}
    jax.profiler.stop_trace()
    rec = load(str(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    return win, rec


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--record", default=None)
    ap.add_argument("--record-ms", type=float, default=15.0)
    args = ap.parse_args(argv)

    from bench import device as device_mod, harness
    from repro.runtime.compile_cache import enable_compile_cache
    cell = harness.load_cell(args.workload)
    device = device_mod.device_gate(cell.chips)
    enable_compile_cache()
    log = device_mod.CompileLog()
    b, _ = harness.prepare(cell, args.seed, log)
    setup_s = b.rec.times[-1] - T_START
    win, rec = traced_window(b, args.seconds, log, TRACE_DIR)
    if args.record:
        Path(args.record).write_text(json.dumps(cut(rec, args.record_ms)))
    tr = reduce(rec)
    ctx = harness.Context(cell=cell, win=win, rec=b.rec, trace=tr,
                          n_params=cell.family.n_params(cell.config),
                          peaks=device_mod.peaks_for(device["kind"]))
    metrics = harness.per_layer(ctx)
    for name, unit in METRICS.items():
        value = harness.load_module(
            harness.HERE / "metrics" / f"{name}.py").read(ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": unit}
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "device": device,
        "setup_s": setup_s,
        "window": {k: win[k] for k in ("window_s", "versions", "updates",
                                        "window_compiles")},
        "busy_s": tr.busy_s, "metrics": metrics,
        "counters": win.get("counters"),
        "spans": {n: {"self_ms": 1e3 * s, "count": tr.count[n]}
                  for n, s in sorted(tr.self_s.items(),
                                     key=lambda kv: -kv[1])},
        "idle_s": dict(sorted(tr.idle_by_label().items(),
                              key=lambda kv: -kv[1])),
        "breakdown": tr.breakdown()}), flush=True)


if __name__ == "__main__":
    main()
