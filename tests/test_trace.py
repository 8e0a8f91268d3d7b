"""The federation's spans and counters (``repro.runtime.trace``): a tiny
top-k + int8 federation traced by the JAX profiler shows every span,
nested where the work nests and tied by worker and version; the two
counters equal what the run's shapes and schedule imply; and neither
changes a single bit of a run."""
import glob
import json
import os
from pathlib import Path

import jax
import pytest
from jax.profiler import ProfileData

from repro.core import TABLE_4_1, make_setup, run_fl
from repro.runtime import trace

GOLDEN_DIR = Path(__file__).resolve().parent / "golden"
W, ROUNDS = 3, 3
TOPK = dict(mode="sync", selector="all", transport="topk_ef+int8",
            transport_frac=0.1, epochs_per_round=1)
SPANS = {"fl.dispatch", "fl.select", "fl.encode_down", "fl.decode_down",
         "fl.train", "fl.encode_up", "fl.respond", "fl.decode_up",
         "fl.merge", "fl.eval", "fl.sync"}


def _setup():
    return make_setup([1] * W, seed=0, n_test=64)


def _spans(trace_dir):
    """``(name, start, end, line, args)`` of every ``fl.`` host event."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     (plane.name, i), dict(e.stats))
                    for e in line.events if e.name.startswith("fl.")]
    return out


def _inside(child, spans, parent):
    """True if a ``parent`` span on ``child``'s line encloses it."""
    _, s, e, line, _ = child
    return any(n == parent and ln == line and ps <= s and e <= pe
               for n, ps, pe, ln, _ in spans)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    setup = _setup()
    run_fl(setup, max_rounds=1, **TOPK)      # compile outside the trace
    d = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(d)
    try:
        hist = run_fl(setup, max_rounds=ROUNDS, **TOPK)
    finally:
        jax.profiler.stop_trace()
    return hist, _spans(d)


def test_every_span_appears_with_its_args(traced):
    _, spans = traced
    assert {n for n, *_ in spans} == SPANS
    need = {"fl.dispatch": {"version"}, "fl.select": {"pool"},
            "fl.encode_down": {"worker"}, "fl.decode_down": {"worker"},
            "fl.train": {"worker", "version"},
            "fl.encode_up": {"worker"},
            "fl.respond": {"worker", "version"},
            "fl.decode_up": {"worker"}, "fl.merge": {"version", "rows"},
            "fl.eval": {"version"}, "fl.sync": {"site"}}
    for name, *_, args in spans:
        assert need[name] <= set(args), (name, args)
    assert {a["pool"] for n, *_, a in spans if n == "fl.select"} == {W}
    assert {a["rows"] for n, *_, a in spans if n == "fl.merge"} == {W}
    assert ({a["version"] for n, *_, a in spans if n == "fl.merge"}
            == set(range(1, ROUNDS + 1)))


def test_spans_nest_where_the_work_nests(traced):
    _, spans = traced
    for sp in spans:
        name = sp[0]
        if name == "fl.sync":
            assert (_inside(sp, spans, "fl.encode_up")
                    or _inside(sp, spans, "fl.encode_down")), sp
        if name in ("fl.decode_up", "fl.merge"):
            assert _inside(sp, spans, "fl.respond"), sp
        if name in ("fl.select", "fl.encode_down"):
            assert _inside(sp, spans, "fl.dispatch"), sp
    # every training's kept count waits inside its uplink encode
    ups = [sp for sp in spans if sp[0] == "fl.encode_up"]
    syncs = [sp for sp in spans if sp[0] == "fl.sync"]
    assert all(any(_inside(s, [u], "fl.encode_up") for s in syncs)
               for u in ups)


def test_worker_and_version_tie_one_update(traced):
    hist, spans = traced
    trains = {(a["worker"], a["version"]) for n, *_, a in spans
              if n == "fl.train"}
    responds = {(a["worker"], a["version"]) for n, *_, a in spans
                if n == "fl.respond"}
    assert responds == trains
    assert len(trains) == W * ROUNDS
    assert {v for _, v in trains} == set(range(ROUNDS))
    assert len(hist) == ROUNDS + 1


def test_counters_match_the_schedule():
    """Sync top-k + int8 both ways with FedAdam, W workers, R rounds:
    one kept count per compressed encode (every uplink; every downlink
    but each worker's first, which ships the raw model), one evaluation
    per version and one at start; the host copies are each worker's f32
    images and int32 labels once, at its first training, each merge's W
    weights and the six FedAdam scalars."""
    setup = _setup()
    before = trace.counters()
    run_fl(setup, max_rounds=ROUNDS, server_opt="fedadam", **TOPK)
    after = trace.counters()
    got = {k: after[k] - before.get(k, 0) for k in after}
    evals, ups, downs = ROUNDS + 1, W * ROUNDS, W * (ROUNDS - 1)
    assert got["host_syncs"] == evals + ups + downs
    per_training = sum(s["x"].size * 4 + s["y"].size * 4
                       for s in setup.shards) // W
    assert got["h2d_bytes"] == W * per_training + ROUNDS * (4 * W + 6 * 4)
    assert got["shard_uploads"] == W
    assert got["shard_hits"] == W * (ROUNDS - 1)


def test_a_traced_run_is_bit_identical(traced):
    hist, _ = traced
    plain = run_fl(_setup(), max_rounds=ROUNDS, **TOPK)
    rec = lambda h: [(p.time.hex(), p.version, float(p.accuracy).hex(),
                      p.n_updates, p.up_bytes, p.down_bytes) for p in h]
    assert rec(plain) == rec(hist)


def _golden_generator():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "golden_generate", GOLDEN_DIR / "generate.py")
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    return gen


@pytest.mark.parametrize("mode", ["sync", "async", "async_delta",
                                  "time_based"])
def test_no_session_keeps_the_golden_histories(mode):
    """The pinned histories were recorded under JAX's earlier
    ``jax_threefry_partitionable=False``; under it the raw fixtures hold
    bit for bit with the spans and counters in place."""
    gen = _golden_generator()
    golden = json.loads((GOLDEN_DIR / "histories.json").read_text())
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with jax.threefry_partitionable(False):
        setup = make_setup(TABLE_4_1["mnist_even"], **gen.SETUP_KW)
        h = run_fl(setup, epochs_per_round=gen.EP, max_rounds=gen.ROUNDS,
                   **gen.MODES[mode], transport="raw")
    assert gen.history_record(h) == golden[f"raw/{mode}"]


def test_span_is_a_shared_no_op_without_a_session():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert trace.span("fl.x", worker="w") is trace.span("fl.y")
    snap = trace.counters()
    snap["host_syncs"] = -1
    assert trace.counters().get("host_syncs") != -1
