"""The reduction of the program's spans (``bench/tracing.py``): self time
and idle time by innermost span, by hand on a small made-up record, the
sweep against a plain scan, the span and counter readers with and without what
they read, and a record cut from a TPU v5e trace of the top-k cell
(``bench/spans.py --record``)."""
import json
import random
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness, spans, tracing

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "trace_spans.json"
METRICS = Path(__file__).resolve().parents[1] / "metrics"
# the readers of the program's spans and counters
READERS = ("codec_host_ms_per_update", "sync_wait_ms_per_update",
           "merge_host_ms_per_version", "control_host_ms_per_update",
           "host_syncs_per_update", "h2d_kb_per_update")

# window 0..200 ns on one host line; device ops 12-30, 60-65, 112-118,
# 140-145.  fl.train 0-40 holds train_fn 5-35; fl.encode_up 40-80 holds
# fl.sync 50-70; fl.respond 90-160 holds fl.decode_up 95-105, fl.merge
# 110-130 and fl.eval 135-150, which holds eval_fn 137-148
HOST = "/host:CPU#0"
HAND = {
    "devices": {"/device:TPU:0": {
        "ops": [["a", 12, 18], ["b", 60, 5], ["c", 112, 6], ["d", 140, 5]],
        "modules": [["jit_cnn_sgd_train(1)", 12, 18],
                    ["jit__kept_count(2)", 60, 5]]}},
    "host": [["bench_window", 0, 200], ["train_fn", 5, 30],
             ["eval_fn", 137, 11]],
    "spans": [["fl.train", HOST, 0, 40], ["train_fn", HOST, 5, 30],
              ["fl.encode_up", HOST, 40, 40], ["fl.sync", HOST, 50, 20],
              ["fl.respond", HOST, 90, 70], ["fl.decode_up", HOST, 95, 10],
              ["fl.merge", HOST, 110, 20], ["fl.eval", HOST, 135, 15],
              ["eval_fn", HOST, 137, 11]],
}


def _without_spans(rec):
    return {k: v for k, v in rec.items() if k != "spans"}


def test_self_time_and_idle_by_span_by_hand():
    r = tracing.reduce(HAND)
    ns = {k: v * 1e9 for k, v in r.self_s.items()}
    assert ns == pytest.approx({"fl.train": 10, "fl.encode_up": 20,
                                "fl.sync": 20, "fl.respond": 25,
                                "fl.decode_up": 10, "fl.merge": 20,
                                "fl.eval": 4})
    assert set(r.count.values()) == {1}
    # gaps 0-12, 30-60, 65-112, 118-140 and 145-200, cut at span edges:
    # e.g. 65-112 is fl.sync 65-70, fl.encode_up 70-80, no span 80-90,
    # fl.respond 90-95, fl.decode_up 95-105, fl.respond 105-110, fl.merge
    # 110-112
    assert {k: v * 1e9 for k, v in r.idle_by_label().items()} == \
        pytest.approx({"fl.train": 10, "train_fn": 12, "fl.encode_up": 20,
                       "fl.sync": 15, "loop": 50, "fl.respond": 25,
                       "fl.decode_up": 10, "fl.merge": 14, "fl.eval": 4,
                       "eval_fn": 6})
    assert len(r.breakdown()["idle_gaps"]) == 10
    # the device side does not depend on the spans
    base = tracing.reduce(_without_spans(HAND))
    assert (r.busy_s, r.window_s, r.module_s) == (
        base.busy_s, base.window_s, base.module_s)
    assert sum(s for _, s in r.gaps) == pytest.approx(
        sum(s for _, s in base.gaps))


def _nested(rng, lo, hi, depth, out):
    """Random spans nested inside ``[lo, hi)``."""
    t = lo
    while depth and t < hi - 2:
        s = rng.uniform(t, hi)
        e = rng.uniform(s, hi)
        if e - s > 1e-3:
            out.append((f"s{len(out)}", HOST, s, e))
            _nested(rng, s, e, depth - 1, out)
        t = e
    return out


def test_sweep_matches_a_plain_scan():
    """Idle by label against cutting every gap at every span edge and
    scanning all spans for each piece."""
    rng = random.Random(3)
    for _ in range(20):
        sp = _nested(rng, 0.0, 100.0, 4, [])
        cuts = sorted(rng.uniform(0, 100) for _ in range(40))
        gaps = list(zip(cuts[::2], cuts[1::2]))
        want = {}
        for a, b in gaps:
            ts = sorted({a, b} | {t for _, _, s, e in sp for t in (s, e)
                                  if a < t < b})
            for u, v in zip(ts, ts[1:]):
                mid = (u + v) / 2
                around = [x for x in sp if x[2] <= mid < x[3]]
                lab = max(around, key=lambda x: x[2])[0] if around \
                    else "loop"
                want[lab] = want.get(lab, 0.0) + v - u
        got = {}
        for lab, ns in tracing.label_gaps(gaps, sp):
            got[lab] = got.get(lab, 0.0) + ns
        assert got == pytest.approx(want)


def _read(name, ctx):
    return harness.load_module(METRICS / f"{name}.py").read(ctx)


def test_readers():
    r = tracing.reduce(HAND)
    win = {"updates": 2, "versions": 1,
           "counters": {"host_syncs": 3, "h2d_bytes": 3_768_000}}
    ctx = SimpleNamespace(trace=r, win=win)
    got = {n: _read(n, ctx) for n in READERS}
    assert got == pytest.approx({
        "codec_host_ms_per_update": 1e-6 * (20 + 10) / 2,
        "sync_wait_ms_per_update": 1e-6 * 20 / 2,
        "merge_host_ms_per_version": 1e-6 * 20,
        "control_host_ms_per_update": 1e-6 * 25 / 2,
        "host_syncs_per_update": 1.5,
        "h2d_kb_per_update": 1884.0})


def test_readers_find_nothing_in_a_program_without_spans():
    """A program without ``fl.`` spans or counters, its record with or
    without the benchmark's own spans: each reader returns None and the
    line leaves the metric out."""
    bench_only = dict(HAND, spans=[s for s in HAND["spans"]
                                   if not s[0].startswith("fl.")])
    for rec in (_without_spans(HAND), bench_only):
        ctx = SimpleNamespace(trace=tracing.reduce(rec),
                              win={"updates": 2, "versions": 1})
        assert {n: _read(n, ctx) for n in READERS} == dict.fromkeys(READERS)


def test_cut_keeps_what_overlaps_the_start():
    c = spans.cut(HAND, 100e-6)             # the first 100 ns
    assert [e[0] for e in c["spans"]] == [
        "fl.train", "train_fn", "fl.encode_up", "fl.sync", "fl.respond",
        "fl.decode_up"]
    assert ["bench_window", 0, 100] in c["host"]
    assert len(c["devices"]["/device:TPU:0"]["ops"]) == 2


def test_recorded_trace():
    """15 ms of a traced window of ``mnist-xdev-sync-topk`` on a TPU v5e:
    the device side reduces as ``tracing`` has it, the spans nest on the
    main thread, and the idle time is named by the program's spans."""
    rec = json.loads(FIXTURE.read_text())
    r, base = tracing.reduce(rec), tracing.reduce(_without_spans(rec))
    assert (r.busy_s, r.module_s) == (base.busy_s, base.module_s)
    assert sum(s for _, s in r.gaps) == pytest.approx(r.window_s - r.busy_s)
    assert {"fl.train", "fl.encode_up", "fl.sync"} <= set(r.count)
    assert all(0 < s <= r.window_s for s in r.self_s.values())
    labels = set(r.idle_by_label())
    assert labels & {n for n in labels if n.startswith("fl.")}
    lines = {line for _, line, _, _ in rec["spans"]}
    assert len(lines) == 1
