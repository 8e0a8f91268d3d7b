"""Transport-codec encode microbenchmark: the fused flat-buffer topk+int8
path (one pass over the packed f32 vector, ``kernels/topk_quant``) vs the
per-leaf pytree ``ErrorFeedbackCompressor`` reference (leaf-local top-k +
quantise, forced via its REPRO_AGG_PATH=tree branch).

Config: a ~1.07M-param ragged-leaf model; each "encode" is one worker
update being prepared for the uplink. Reports ms/encode and exact
bytes/update for every codec in the registry.

Emits ``benchmarks/results/BENCH_wire.json``. Run directly or via
``benchmarks/run.py``.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

RESULTS = Path(__file__).resolve().parent / "results"

ROUNDS = 20        # timed encodes per path
HIDDEN = 1024      # ~1.07M params total
FRAC = 0.1


def _model(seed: int):
    import jax
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    t = {
        "w1": jax.random.normal(ks[0], (784, HIDDEN)) * 0.05,
        "b1": jax.random.normal(ks[1], (HIDDEN,)) * 0.05,
        "w2": jax.random.normal(ks[2], (HIDDEN, 256)) * 0.05,
        "b2": jax.random.normal(ks[3], (256,)) * 0.05,
        "w3": jax.random.normal(ks[4], (256, 10)) * 0.05,
        "b3": jax.random.normal(ks[5], (10,)) * 0.05,
    }
    jax.block_until_ready(t)
    return t


def _time_encode(step, rounds: int = ROUNDS) -> float:
    import jax
    out = step(0)                       # warmup: jit traces
    out = step(1)
    jax.block_until_ready(jax.tree.leaves(out))
    t0 = time.perf_counter()
    for i in range(rounds):
        out = step(2 + i)
    jax.block_until_ready(jax.tree.leaves(out))
    return (time.perf_counter() - t0) / rounds


def run() -> dict:
    import jax
    import jax.numpy as jnp
    from repro.core import transport
    from repro.core.compression import ErrorFeedbackCompressor

    base = _model(0)
    news = [_model(1 + i) for i in range(2 + ROUNDS)]
    n_params = sum(l.size for l in jax.tree.leaves(base))

    # fused flat path: pack -> one-pass threshold+quantise kernel
    tr = transport.Transport(base, codec="topk_ef+int8", frac=FRAC)
    link = tr.link("bench")
    link.encode_down(base)

    def fused_step(i):
        p = link.encode_up(news[i % len(news)])
        return p.data

    # per-leaf reference: leaf-local top-k + per-tensor scales (tree branch)
    comp = ErrorFeedbackCompressor(frac=FRAC, quantize=True)
    deltas = [jax.tree.map(lambda n, b: n - b, t, base) for t in news]

    def tree_step(i):
        recon, _ = comp._compress_tree(deltas[i % len(deltas)])
        return recon

    t_fused = _time_encode(fused_step)
    t_tree = _time_encode(tree_step)

    bytes_per_update = {
        name: (transport.Transport(base, codec=name, frac=FRAC)
               .expected_up_bytes())
        for name in transport.CODECS
    }

    rec = {
        "config": {"n_params": int(n_params), "frac": FRAC, "rounds": ROUNDS,
                   "backend": jax.default_backend()},
        "fused_flat_encode_ms": round(t_fused * 1e3, 3),
        "per_leaf_tree_encode_ms": round(t_tree * 1e3, 3),
        "speedup": round(t_tree / t_fused, 2),
        "bytes_per_update": bytes_per_update,
        "uplink_ratio_vs_raw": {
            name: round(bytes_per_update["raw"] / b, 2)
            for name, b in bytes_per_update.items()},
    }
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / "BENCH_wire.json").write_text(json.dumps(rec, indent=2))
    return rec


def main() -> None:
    rec = run()
    print("== Wire codec encode: fused flat kernel vs per-leaf tree-map ==")
    print(f"n_params={rec['config']['n_params']} frac={rec['config']['frac']} "
          f"backend={rec['config']['backend']}")
    print(f"per-leaf tree encode: {rec['per_leaf_tree_encode_ms']:.3f} ms")
    print(f"fused flat encode:    {rec['fused_flat_encode_ms']:.3f} ms")
    print(f"speedup:              {rec['speedup']}x")
    print("bytes/update:", json.dumps(rec["bytes_per_update"]))
    print("vs raw:      ", json.dumps(rec["uplink_ratio_vs_raw"]))


if __name__ == "__main__":
    main()
