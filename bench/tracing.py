"""From the profiler's trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps a
plain record of it: each device's operations and programs, the window
the benchmark opens (``bench_window``), and the host spans, each with
its thread line: the program's own (``fl.*``, from
``repro.runtime.trace``) and the benchmark's ``train_fn`` and
``eval_fn``.  ``reduce`` turns that record into

* the device's busy time: the union of its operations' intervals inside
  the window, averaged over the devices used;
* device time and runs by program name;
* the idle gaps, cut where host spans begin and end, each piece labelled
  by the innermost host span around it (``loop`` where none is);
* the self time (a span's duration less what its child spans on the
  same line cover) and the count of each ``fl.*`` span in the window.

The record is plain JSON, so a small recorded trace can stand as a test
fixture (``bench/tests/fixtures/``).  ``span_ms`` and ``counted`` are
what the readers of the program's spans and counters share.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

PREFIX = "fl."
BENCH_SPANS = ("train_fn", "eval_fn")
WINDOW = "bench_window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
BREAKDOWN_ENTRIES = 10       # at most, in each list of the breakdown

Event = Tuple[str, float, float]          # name, start ns, duration ns


def load(trace_dir: str) -> dict:
    """The plain record of the newest ``.xplane.pb`` under ``trace_dir``:
    ``devices`` (operations and programs by device), ``host`` (``[name,
    start ns, duration ns]`` of the window) and ``spans`` (``[name, line,
    start ns, duration ns]`` of each ``fl.*``, ``train_fn`` and
    ``eval_fn`` event on the host)."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices: Dict[str, dict] = {}
    host: List[Event] = []
    spans: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            rec = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is not None:
                    rec[key].extend((e.name, float(e.start_ns),
                                     float(e.duration_ns))
                                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    ev = (e.name, float(e.start_ns), float(e.duration_ns))
                    if e.name == WINDOW:
                        host.append(ev)
                    elif e.name.startswith(PREFIX) or e.name in BENCH_SPANS:
                        spans.append([e.name, f"{plane.name}#{i}", *ev[1:]])
    return {"devices": devices, "host": host, "spans": spans}


def union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length of the union of ``(start, end)`` intervals."""
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def clip(events: Iterable[Event], lo: float, hi: float) -> List[tuple]:
    """``(name, start, end)`` of each event, cut to ``[lo, hi]``."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b))
    return out


def gaps(busy: Sequence[tuple], lo: float, hi: float) -> List[tuple]:
    """``(start, end)`` of each stretch of ``[lo, hi]`` that no busy
    interval covers."""
    out, t = [], lo
    for s, e in sorted((s, e) for _, s, e in busy):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


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


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    module_s: Dict[str, float] = field(default_factory=dict)
    module_n: Dict[str, float] = field(default_factory=dict)
    gaps: List[tuple] = field(default_factory=list)    # (label, seconds)
    self_s: Dict[str, float] = field(default_factory=dict)   # fl.* spans
    count: Dict[str, int] = field(default_factory=dict)

    def time_of(self, patterns: Sequence[str]) -> float:
        """Device seconds of the programs whose name holds any of
        ``patterns``, averaged over the devices."""
        return sum(s for name, s in self.module_s.items()
                   if any(p in name for p in patterns))

    def calls_of(self, patterns: Sequence[str]) -> float:
        """Runs of those programs in the window, averaged over devices."""
        return sum(c for name, c in self.module_n.items()
                   if any(p in name for p in patterns))

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def idle_by_label(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for lab, s in self.gaps:
            out[lab] = out.get(lab, 0.0) + s
        return out

    def breakdown(self) -> dict:
        """The programs that took most device time, and the idle time:
        each label's total, largest first, then the longest single
        pieces, ``BREAKDOWN_ENTRIES`` at most in each list."""
        n = BREAKDOWN_ENTRIES
        ops = sorted(self.module_s.items(), key=lambda kv: -kv[1])[:n]
        by_label = sorted(self.idle_by_label().items(), key=lambda kv: -kv[1])
        longest = sorted(self.gaps, key=lambda g: -g[1])
        idle = [[f"all {lab}", s] for lab, s in by_label[:n]]
        idle += [[f"gap {lab}", s] for lab, s in longest[:n - len(idle)]]
        return {"device_ops": [[name, s] for name, s in ops],
                "idle_gaps": idle}


def reduce(rec: dict) -> Reduced:
    """The window's busy time, program times, idle gaps labelled by the
    innermost host span, and each ``fl.*`` span's self time and count."""
    windows = [(s, s + d) for name, s, d in rec["host"] if name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found "
                         f"{len(windows)}")
    lo, hi = windows[0]
    spans = [(n, line, max(s, lo), min(s + d, hi))
             for n, line, s, d in rec.get("spans", ())
             if min(s + d, hi) > max(s, lo)]
    devices = [d for d in rec["devices"].values() if d["ops"]]
    if not devices:
        raise ValueError("the trace holds no device operations")
    n = len(devices)
    busy = 0.0
    module_s: Dict[str, float] = {}
    module_n: Dict[str, float] = {}
    all_gaps: List[tuple] = []
    for dev in devices:
        ops = clip(dev["ops"], lo, hi)
        busy += union_ns((s, e) for _, s, e in ops)
        for name, s, e in clip(dev["modules"], lo, hi):
            module_s[name] = module_s.get(name, 0.0) + (e - s) / 1e9 / n
            module_n[name] = module_n.get(name, 0.0) + 1.0 / n
        all_gaps += [(lab, ns / 1e9)
                     for lab, ns in label_gaps(gaps(ops, lo, hi), spans)]
    self_s, count = self_times(spans)
    return Reduced(window_s=(hi - lo) / 1e9, busy_s=busy / 1e9 / n,
                   module_s=module_s, module_n=module_n, gaps=all_gaps,
                   self_s={k: v for k, v in self_s.items()
                           if k.startswith(PREFIX)},
                   count={k: v for k, v in count.items()
                          if k.startswith(PREFIX)})


# --- what the readers of the program's spans and counters share -------------

def span_ms(ctx, names: Sequence[str], per: str) -> Optional[float]:
    """Milliseconds of self time of the spans ``names`` in the traced
    window, per ``per`` (``"updates"`` or ``"versions"``); None where the
    trace holds none of them."""
    self_s = ctx.trace.self_s
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
