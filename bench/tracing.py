"""From the profiler's trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps a
plain record of it: each device's operations and programs, and the host
spans the benchmark opens (``train_fn``, ``eval_fn`` and the window
itself, ``bench_window``).  ``reduce`` turns that record into

* the device's busy time: the union of its operations' intervals inside
  the window, averaged over the devices used;
* device time by program name (summed durations inside the window);
* the idle gaps, each labelled by the host span it fell in: ``train_fn``,
  ``eval_fn``, or ``loop`` for the rest of the program's host work.

The record is plain JSON, so a small recorded trace can stand as a test
fixture (``bench/tests/fixtures/``).
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

HOST_SPANS = ("train_fn", "eval_fn", "bench_window")
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Event = Tuple[str, float, float]          # name, start ns, duration ns


def load(trace_dir: str) -> dict:
    """The plain record of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices: Dict[str, dict] = {}
    host: List[Event] = []
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
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events if e.name in HOST_SPANS)
    return {"devices": devices, "host": host}


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


def label(t: float, spans: Sequence[tuple]) -> str:
    """The host span ``t`` falls in (``train_fn``, ``eval_fn``), else the
    loop."""
    for name, s, e in spans:
        if s <= t < e and name != "bench_window":
            return name
    return "loop"


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    module_s: Dict[str, float] = field(default_factory=dict)
    module_n: Dict[str, float] = field(default_factory=dict)
    gaps: List[tuple] = field(default_factory=list)    # (label, seconds)

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
        ops = sorted(self.module_s.items(), key=lambda kv: -kv[1])[:10]
        by_label = sorted(self.idle_by_label().items(), key=lambda kv: -kv[1])
        longest = sorted(self.gaps, key=lambda g: -g[1])
        idle = [[f"all {lab}", s] for lab, s in by_label]
        idle += [[f"gap {lab}", s] for lab, s in longest[:10 - len(idle)]]
        return {"device_ops": [[n, s] for n, s in ops], "idle_gaps": idle}


def reduce(rec: dict) -> Reduced:
    """The window's busy time, program times and labelled idle gaps."""
    windows = [(s, s + d) for name, s, d in rec["host"]
               if name == "bench_window"]
    if len(windows) != 1:
        raise ValueError(f"expected one bench_window span, found "
                         f"{len(windows)}")
    lo, hi = windows[0]
    spans = clip(rec["host"], lo, hi)
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
        all_gaps += [(label((a + b) / 2, spans), (b - a) / 1e9)
                     for a, b in gaps(ops, lo, hi)]
    return Reduced(window_s=(hi - lo) / 1e9, busy_s=busy / 1e9 / n,
                   module_s=module_s, module_n=module_n, gaps=all_gaps)
