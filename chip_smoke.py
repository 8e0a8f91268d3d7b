"""Run the federation's main path once on a TPU and check what comes out.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded merge, on four chips

One chip.  The thesis CNN at its published width (28x28x1 inputs,
conv16/conv32) federates through ``make_setup``/``run_fl`` twice: sync
with Algorithm 2's time-based selector, and async.  Both runs use the
``topk_ef+int8`` transport and the FedAdam server optimizer, so every
round runs the merge, codec and server-optimizer Pallas kernels natively.
Each history must stay finite, advance its version and rise in accuracy.
Then the same kernels run once at a 16.8M-parameter width (a W=64 row
buffer) and are checked against float64 NumPy at f32 tolerance.

Four chips (``--chips 4``, this phase only).  The sync run at
``server_mesh=4`` against ``server_mesh=1`` under raw and compressed
transport, and the 16.8M merge sharded over four chips against one chip,
all bit for bit.

JAX must find a TPU: on any other platform the script exits non-zero
before any work.  The last line of standard output is one JSON object
naming the device.  The times printed on the way are set-up diagnostics,
not benchmark numbers.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.agg_shard_bench import MODELS  # noqa: E402
from repro import kernels  # noqa: E402
from repro.configs.paper_cnn import MNIST_CNN  # noqa: E402
from repro.core import TABLE_4_1, flatbuf, make_setup, run_fl  # noqa: E402
from repro.core import transport as tp  # noqa: E402
from repro.kernels import fedavg_agg, topk_quant  # noqa: E402
from repro.parallel import sharding as psharding  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

# --- main path: the thesis CNN through run_fl ------------------------------
FRAC = 0.1
FL_COMMON = dict(transport="topk_ef+int8", transport_frac=FRAC,
                 server_opt="fedadam", server_opt_kw={"lr": 0.03},
                 epochs_per_round=50)
FL_RUNS = {
    "sync": dict(mode="sync", selector="time_based",
                 selector_kw={"r": 10, "T0": 0.0, "A": 0.01}, max_rounds=8),
    "async": dict(mode="async", max_rounds=40),
}
MIN_RISE = 0.05      # final accuracy over version 0's; chance sd is ~0.013

# --- kernels at a real width ------------------------------------------------
KERNEL_SPEC = MODELS["mlp_16m"]
KERNEL_W = 64        # update rows in the merge
UNIQUE_VECS = 16     # distinct update vectors, cycled over the rows
ALPHA = 0.5
FEDADAM = np.asarray([0.9, 0.99, 0.03, 1e-3, 0.0, 0.0], np.float32)
FEDAVGM = np.asarray([0.9, 1.0, 0.0, 1.0], np.float32)
EPS32 = float(np.finfo(np.float32).eps)
TINY = float(np.finfo(np.float32).tiny)

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


class SmokeFailure(Exception):
    pass


def require(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def device_gate(chips: int) -> dict:
    """The device as JAX reports it; exits unless it is a TPU with the
    native Pallas path and enough chips."""
    devs = jax.devices()
    d0 = devs[0]
    print(f"jax {jax.__version__}: platform {d0.platform}, "
          f"device_kind {d0.device_kind!r}, {len(devs)} device(s)")
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {d0.platform!r}")
    for var in ("REPRO_FLAT_PALLAS", "REPRO_AGG_PATH"):
        if var in os.environ:
            sys.exit(f"chip_smoke: {var} is set; unset it to run the "
                     "default path")
    flags = kernels.pallas_flags(None, None)
    if flags != (True, False):
        sys.exit(f"chip_smoke: kernels resolve to (use_pallas, interpret)"
                 f"={flags}, not native Pallas")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips}, but JAX sees {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


class CompileLog:
    """Compile seconds and persistent-cache hits/misses, as reported by
    JAX's monitoring events."""

    def __init__(self):
        self.secs, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == BACKEND_COMPILE:
            self.secs += secs

    def _event(self, event, **_):
        self.hits += event == CACHE_HIT
        self.misses += event == CACHE_MISS

    def snapshot(self):
        return self.secs, self.hits, self.misses


@contextlib.contextmanager
def phase(name: str, log: CompileLog):
    print(f"== {name}", flush=True)
    c0, h0, m0 = log.snapshot()
    t0 = time.perf_counter()
    yield
    c1, h1, m1 = log.snapshot()
    print(f"== {name}: wall {time.perf_counter() - t0:.2f} s, compile "
          f"{c1 - c0:.2f} s, cache hits {h1 - h0}, misses {m1 - m0}",
          flush=True)


def _cnn_setup():
    return make_setup(TABLE_4_1["mnist_even"], model="cnn", cfg=MNIST_CNN)


def check_history(name: str, hist) -> None:
    for p in hist:
        print(f"  {name}: t={p.time:.4f} version={p.version} "
              f"acc={p.accuracy:.4f} n_updates={p.n_updates} "
              f"up={p.up_bytes} down={p.down_bytes}")
    require(len(hist) >= 2, f"{name}: history has {len(hist)} points")
    require(all(np.isfinite([p.time, p.accuracy]).all() for p in hist),
            f"{name}: non-finite time or accuracy")
    vs = [p.version for p in hist]
    require(all(a <= b for a, b in zip(vs, vs[1:])) and vs[-1] > vs[0],
            f"{name}: versions do not advance: {vs}")
    a0, a1 = hist[0].accuracy, hist[-1].accuracy
    require(a1 >= a0 + MIN_RISE,
            f"{name}: accuracy {a0:.4f} -> {a1:.4f} did not rise by "
            f"{MIN_RISE}")


def phase_main_path() -> None:
    setup = _cnn_setup()
    print(f"  thesis CNN {MNIST_CNN.name}: {setup.model_bytes} bytes, "
          f"{len(setup.profiles)} workers")
    for name, kw in FL_RUNS.items():
        t0 = time.perf_counter()
        hist = run_fl(_cnn_setup(), **FL_COMMON, **kw)
        print(f"  {name}: {len(hist)} points in "
              f"{time.perf_counter() - t0:.2f} s")
        check_history(name, hist)


# --- float64 checks ---------------------------------------------------------

def _f64(x) -> np.ndarray:
    return np.asarray(x).astype(np.float64)


def check_close(name: str, got, ref: np.ndarray, bound: np.ndarray) -> None:
    """``|got - ref| <= bound`` elementwise; ``bound`` is an f32 rounding
    bound of the formula (zero where the exact result is exactly zero)."""
    g = _f64(got)
    require(g.shape == ref.shape, f"{name}: shape {g.shape} != {ref.shape}")
    require(np.isfinite(g).all(), f"{name}: non-finite output")
    err = np.abs(g - ref)
    worst = float(np.max(err / np.maximum(bound, TINY)))
    print(f"  {name}: max |err| {float(err.max()):.3e}, worst err / f32 "
          f"bound {worst:.4f}")
    require(worst <= 1.0, f"{name}: error beyond f32 tolerance "
                          f"({worst:.1f}x the bound)")


def _random_tree(spec: dict, seed: int):
    ks = jax.random.split(jax.random.PRNGKey(seed), len(spec))
    return {n: jax.random.normal(k, s, jnp.float32) * 0.05
            for k, (n, s) in zip(ks, spec.items())}


def _timed(fn, *args):
    """(result, seconds of the first call, seconds of a second call)."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    t1 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, t1 - t0, time.perf_counter() - t1


def _merge_inputs(unique, W: int):
    updates = [unique[i % len(unique)] for i in range(W)]
    ws = [1.0 / (1 + i % 3) for i in range(W)]
    return updates, ws


def check_merge(st, template, unique) -> np.ndarray:
    """``merge_rows`` at ALPHA against float64; returns the packed result."""
    b = st.bundle
    updates, ws = _merge_inputs(unique, KERNEL_W)
    merge = functools.partial(st.merge_rows, template, updates, ws, ALPHA)
    out, first, warm = _timed(merge)
    got = np.asarray(b.pack(out))
    print(f"  merge_rows W={KERNEL_W} N={b.padded_size}: first call "
          f"{first:.3f} s, second {warm:.3f} s")
    # the f32 weight vector the merge hands the kernel, exactly
    coef = np.zeros(KERNEL_W + 1, np.float32)
    coef[0] = 1.0 - ALPHA
    coef[1:] = ALPHA * flatbuf.normalized_weights(ws)
    server = _f64(b.pack(template))
    ref, mag = coef[0] * server, abs(float(coef[0])) * np.abs(server)
    for u, vec in enumerate(unique):
        c = coef[1:][np.arange(KERNEL_W) % len(unique) == u].astype(
            np.float64)
        x = _f64(vec)
        ref += c.sum() * x
        mag += np.abs(c).sum() * np.abs(x)
    check_close("merge_rows", got, ref, (KERNEL_W + 2) * EPS32 * mag)
    return got


def check_codec(unique, n_params: int) -> None:
    x = unique[0] - unique[1]
    # the codec's own threshold and int8 scale for this delta
    (_, scale), *_ = tp.ef_topk_encode(x, n_params=n_params, frac=FRAC,
                                       quantize=True)
    thresh = tp.topk_threshold(x, tp.topk_k(n_params, FRAC), n_params)
    (q, resid), first, warm = _timed(topk_quant.topk_quant_encode, x,
                                     thresh, scale)
    print(f"  topk_quant_encode: first call {first:.3f} s, second "
          f"{warm:.3f} s")
    thresh = float(thresh)
    x64, s64, q64 = _f64(x), float(scale), _f64(q)
    ratio = x64 / s64
    q_ref = np.where(np.abs(x64) >= thresh,
                     np.clip(np.round(ratio), -127, 127), 0.0)
    off = q64 != q_ref
    near_tie = np.abs(np.abs(ratio) % 1.0 - 0.5) <= 8 * EPS32 * np.abs(ratio)
    print(f"  topk_quant_encode: {int((q64 != 0).sum())} kept, "
          f"{int(off.sum())} codes off by one at a rounding tie")
    require(np.abs(q64 - q_ref).max() <= 1 and near_tie[off].all(),
            "topk_quant_encode: int8 codes differ from float64 rounding")
    check_close("topk_quant residual", resid, x64 - q64 * s64,
                2 * EPS32 * (np.abs(x64) + np.abs(q64 * s64)))

    base = unique[2]
    out, first, warm = _timed(topk_quant.dequant_add, q, scale, base)
    print(f"  dequant_add: first call {first:.3f} s, second {warm:.3f} s")
    b64 = _f64(base)
    check_close("dequant_add", out, b64 + q64 * s64,
                2 * EPS32 * (np.abs(b64) + np.abs(q64 * s64)))


def check_server_opt(prev, merged, m, v) -> None:
    p, mg, m0, v0 = (_f64(a) for a in (prev, merged, m, v))
    d = mg - p
    mag_d = np.abs(mg) + np.abs(p)

    # the backend's Pallas mode (native on the chip, as the gate checks)
    interpret = kernels.pallas_flags(None, None)[1]
    step = jax.jit(functools.partial(fedavg_agg.server_opt_step_flat,
                                     adam=True, interpret=interpret))
    (new, mo, vo), first, warm = _timed(step, prev, merged, m, v,
                                        jnp.asarray(FEDADAM))
    print(f"  server_opt_step_flat adam: first call {first:.3f} s, second "
          f"{warm:.3f} s")
    b1, b2, lr, tau = (float(s) for s in FEDADAM[:4])
    m1 = b1 * m0 + (1 - b1) * d
    v1 = b2 * v0 + (1 - b2) * d * d
    den = np.sqrt(v1) + tau
    upd = lr * m1 / den
    err_m = 4 * EPS32 * (b1 * np.abs(m0) + (1 - b1) * mag_d)
    err_v = 4 * EPS32 * (b2 * np.abs(v0)
                         + (1 - b2) * (d * d + 2 * np.abs(d) * mag_d))
    err_den = (np.divide(err_v, 2 * np.sqrt(v1), out=np.zeros_like(v1),
                         where=v1 > 0) + 2 * EPS32 * den)
    err_new = (EPS32 * (np.abs(p) + np.abs(upd)) + lr * err_m / den
               + np.abs(upd) * (err_den / den + 2 * EPS32))
    check_close("adam m'", mo, m1, err_m)
    check_close("adam v'", vo, v1, err_v)
    check_close("adam new", new, p + upd, err_new)

    step = jax.jit(lambda p, mg, mm, sc: fedavg_agg.server_opt_step_flat(
        p, mg, mm, None, sc, adam=False, interpret=interpret))
    (new, mo, _), first, warm = _timed(step, prev, merged, m,
                                       jnp.asarray(FEDAVGM))
    print(f"  server_opt_step_flat momentum: first call {first:.3f} s, "
          f"second {warm:.3f} s")
    am, bm, cd, lr = (float(s) for s in FEDAVGM)
    m1 = am * m0 + bm * d
    mag_m = am * np.abs(m0) + abs(bm) * mag_d
    check_close("momentum m'", mo, m1, 4 * EPS32 * mag_m)
    check_close("momentum new", new, p + cd * d + lr * m1,
                4 * EPS32 * (np.abs(p) + abs(cd) * mag_d + abs(lr) * mag_m))


def phase_kernels() -> None:
    template = _random_tree(KERNEL_SPEC, 0)
    st = flatbuf.FlatServerState(template)
    b = st.bundle
    unique = [b.pack(_random_tree(KERNEL_SPEC, 1 + i))
              for i in range(UNIQUE_VECS)]
    print(f"  {b.n_params} parameters, row buffer "
          f"{KERNEL_W * b.padded_size * 4 / 1e9:.2f} GB")
    merged = jnp.asarray(check_merge(st, template, unique))
    check_codec(unique, b.n_params)
    check_server_opt(b.pack(template), merged, 0.1 * unique[3],
                     jnp.square(unique[4]))


# --- four chips ---------------------------------------------------------------

def _records(hist):
    return [(p.time.hex(), p.version, float(p.accuracy).hex(), p.n_updates,
             p.selected, p.up_bytes, p.down_bytes) for p in hist]


def compare_histories(name: str, sharded, single) -> None:
    """A sharded server changes no bit of the history: the merge is
    shard-local and workers train on replicated trees."""
    check_history(f"{name} sharded", sharded)
    check_history(f"{name} single", single)
    same = _records(sharded) == _records(single)
    da = max(abs(a.accuracy - b.accuracy) for a, b in zip(sharded, single))
    print(f"  {name}: sharded vs single "
          f"{'bit-identical' if same else 'DIFFERENT'}: max |dacc| {da:.3e}")
    require(same, f"{name}: sharded history differs from single-chip")


def phase_sharded(chips: int) -> None:
    # the codec stages resolve their kernel from the transport's flags, as
    # kernels/topk_quant does: on a >1-device mesh they take the XLA oracle
    t = tp.Transport(_cnn_setup().weights0, codec="topk_ef+int8",
                     mesh=psharding.agg_mesh(chips))
    codec = ("pallas" if kernels.pallas_flags(t.use_pallas, t.interpret)[0]
             else "xla")
    print(f"  codec stages on the {chips}-device mesh: {codec}")
    sync = dict(FL_COMMON, **FL_RUNS["sync"])
    for transport in ("raw", "topk_ef+int8"):
        kw = dict(sync, transport=transport)
        sharded = run_fl(_cnn_setup(), server_mesh=chips, **kw)
        single = run_fl(_cnn_setup(), server_mesh=1, **kw)
        compare_histories(f"sync {transport}", sharded, single)

    template = _random_tree(KERNEL_SPEC, 0)
    trees = [_random_tree(KERNEL_SPEC, 1 + i) for i in range(UNIQUE_VECS)]
    outs = {}
    for d in (1, chips):
        mesh = None if d == 1 else psharding.agg_mesh(d)
        st = flatbuf.FlatServerState(template, mesh=mesh)
        unique = [st.bundle.pack(t) for t in trees]
        updates, ws = _merge_inputs(unique, KERNEL_W)
        out, first, warm = _timed(functools.partial(
            st.merge_rows, template, updates, ws, ALPHA))
        per_dev = st.row_bytes_by_device()
        print(f"  merge_rows W={KERNEL_W} on {d} device(s): first call "
              f"{first:.3f} s, second {warm:.3f} s; row buffer bytes per "
              f"device {per_dev}")
        total = KERNEL_W * st.bundle.padded_size * 4
        require(len(per_dev) == d
                and set(per_dev.values()) == {total // d},
                f"row buffer not split evenly over {d} devices: {per_dev}")
        outs[d] = [np.asarray(leaf) for leaf in jax.tree.leaves(out)]
        del st, unique, updates, out
    same = all(np.array_equal(a, b) for a, b in zip(outs[1], outs[chips]))
    print(f"  merge_rows sharded over {chips} vs one device: "
          f"{'bit-identical' if same else 'DIFFERENT'}")
    require(same, "sharded 16.8M merge differs from the single-chip merge")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-merge phase on four chips")
    args = ap.parse_args(argv)
    device = device_gate(args.chips)
    print(f"compilation cache: {enable_compile_cache()}")
    log = CompileLog()
    if args.chips == 1:
        with phase("main path: thesis CNN through run_fl", log):
            phase_main_path()
        with phase("kernels at 16.8M parameters vs float64", log):
            phase_kernels()
    else:
        with phase(f"sharded merge on {args.chips} chips", log):
            phase_sharded(args.chips)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
