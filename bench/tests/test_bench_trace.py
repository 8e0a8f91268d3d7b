"""The reduction from a profiler trace to busy time, program time and
labelled idle gaps: by hand on a small made-up record, and on a small
record cut from a real trace of a TPU v5e run."""
import json
from pathlib import Path

import pytest

from bench import tracing

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "trace_small.json"

# window 0..100 ns; device ops 10-30, 20-40 (overlap), 60-70;
# host: train_fn 0-50, eval_fn 65-90
HAND = {
    "devices": {"/device:TPU:0": {
        "ops": [["a", 10, 20], ["b", 20, 20], ["c", 60, 10], ["d", 95, 10]],
        "modules": [["jit_cnn_sgd_train(1)", 10, 30],
                    ["jit__weighted_sum(2)", 60, 10]]}},
    "host": [["bench_window", 0, 100], ["train_fn", 0, 50],
             ["eval_fn", 65, 25]],
}


def test_union_and_gaps_by_hand():
    assert tracing.union_ns([(10, 30), (20, 40), (60, 70)]) == 40
    assert tracing.union_ns([(0, 10), (2, 5)]) == 10
    ops = tracing.clip([("a", 10, 20), ("b", 20, 20), ("c", 60, 10)], 0, 100)
    assert tracing.gaps(ops, 0, 100) == [(0, 10), (40, 60), (70, 100)]


def test_reduce_by_hand():
    r = tracing.reduce(HAND)
    assert r.window_s == pytest.approx(100e-9)
    # 10-40, 60-70 and 95-100 (the op running past the window is cut)
    assert r.busy_s == pytest.approx(45e-9)
    assert r.idle_share == pytest.approx(0.55)
    assert r.time_of(["cnn_sgd_train"]) == pytest.approx(30e-9)
    assert r.calls_of(["_weighted_sum"]) == 1
    # gap 0-10 is in train_fn; 40-60 (mid 50) after it, in the loop;
    # 70-95 (mid 82.5) in eval_fn
    assert r.idle_by_label() == pytest.approx(
        {"train_fn": 10e-9, "loop": 20e-9, "eval_fn": 25e-9})
    bd = r.breakdown()
    assert bd["device_ops"][0][0] == "jit_cnn_sgd_train(1)"
    assert len(bd["idle_gaps"]) <= 10


def _naive(rec):
    """Busy nanoseconds by walking the window one nanosecond at a time is
    too slow; walk the sorted event edges instead."""
    (lo, hi), = [(s, s + d) for n, s, d in rec["host"]
                 if n == "bench_window"]
    edges = sorted({lo, hi} | {t for _, s, d in
                               next(iter(rec["devices"].values()))["ops"]
                               for t in (s, s + d) if lo <= t <= hi})
    ops = next(iter(rec["devices"].values()))["ops"]
    busy = 0.0
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        if any(s <= mid < s + d for _, s, d in ops):
            busy += b - a
    return busy / 1e9, (hi - lo) / 1e9


def test_recorded_trace():
    rec = json.loads(FIXTURE.read_text())
    r = tracing.reduce(rec)
    busy, window = _naive(rec)
    assert r.window_s == pytest.approx(window)
    assert r.busy_s == pytest.approx(busy)
    assert 0 < r.busy_s < r.window_s
    assert r.time_of(["cnn_sgd_train"]) > 0
    assert sum(s for _, s in r.gaps) == pytest.approx(window - busy)
    assert set(r.idle_by_label()) <= {"train_fn", "eval_fn", "loop"}
