"""The program's own spans and counters in a traced window of one cell.

    python3 bench/spans.py --workload mnist-xdev-sync-topk --seed 7

Drives the cell's set-up and one traced window as ``bench/run.py --trace
1`` does (``harness.prepare``, ``harness.traced_measure``), reduces the
trace with ``tracing.reduce``, and prints one JSON line: the cell's
per-layer metrics, the program's counters over the window, self time and
count by span, idle seconds by innermost span and the breakdown.
``--record PATH`` also writes the plain record of the window's first
``--record-ms`` milliseconds, for a test fixture.  Runs on the chip,
like ``run.py``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

TRACE_DIR = ROOT / ".bench_trace_spans"


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


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--record", default=None)
    ap.add_argument("--record-ms", type=float, default=15.0)
    args = ap.parse_args(argv)

    from bench import device as device_mod, harness, tracing
    from repro.runtime.compile_cache import enable_compile_cache
    cell = harness.load_cell(args.workload)
    device = device_mod.device_gate(cell.chips)
    enable_compile_cache()
    log = device_mod.CompileLog()
    b, _ = harness.prepare(cell, args.seed, log)
    setup_s = b.rec.times[-1] - T_START
    win, rec = harness.traced_measure(b, args.seconds, log, TRACE_DIR)
    if args.record:
        Path(args.record).write_text(json.dumps(cut(rec, args.record_ms)))
    tr = tracing.reduce(rec)
    ctx = harness.Context(cell=cell, win=win, rec=b.rec, trace=tr,
                          n_params=cell.family.n_params(cell.config),
                          peaks=device_mod.peaks_for(device["kind"]))
    metrics = harness.per_layer(ctx)
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
