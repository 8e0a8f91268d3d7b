"""Sharded aggregation-server microbenchmark: merge latency and per-device
peak live bytes of the (W, N) substrate vs server-mesh size.

Grid: W in {8, 64, 256} worker updates per merge x two model sizes
(~1.07M and ~16.8M params) x mesh sizes {1, 2, 4} — the ISSUE-4
acceptance artifact is the per-device live bytes of the row buffer
shrinking ~linearly with mesh size while the merge stays a single fused
per-shard pass.  Cells whose full (W, N) buffer would exceed the memory
cap (REPRO_BENCH_MEM, default 1.6 GB) are recorded as skipped, never
silently dropped.

Run directly (forces a 4-device host platform when XLA_FLAGS is unset, so
CPU runs exercise real sharding) or via ``benchmarks/run.py`` (whatever
devices the session already has); ``--smoke`` is the CI config.  Emits
``benchmarks/results/BENCH_agg_shard.json``.
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

RESULTS = Path(__file__).resolve().parent / "results"

ALPHA = 0.5
ROUNDS = 5
UNIQUE_VECS = 16         # distinct update vectors cycled across W rows
MEM_CAP = int(float(os.environ.get("REPRO_BENCH_MEM", 1.6e9)))

MODELS = {
    # agg_bench's ~1.07M-param ragged MLP regime
    "mlp_1m": {"w1": (784, 1024), "b1": (1024,), "w2": (1024, 256),
               "b2": (256,), "w3": (256, 10), "b3": (10,)},
    # ~16.8M params: the "big" tier
    "mlp_16m": {"w1": (2048, 4096), "w2": (4096, 2048)},
}
W_GRID = (8, 64, 256)
MESH_GRID = (1, 2, 4)


def _model(spec: dict, seed: int):
    import jax
    ks = jax.random.split(jax.random.PRNGKey(seed), len(spec))
    return {name: jax.random.normal(k, shape) * 0.05
            for k, (name, shape) in zip(ks, spec.items())}


def _bench_cell(name: str, spec: dict, W: int, d: int, rounds: int) -> dict:
    import jax

    from repro.core import flatbuf
    from repro.parallel import sharding as psh

    mesh = psh.agg_mesh(d)
    template = _model(spec, 0)
    st = flatbuf.FlatServerState(template, mesh=mesh)
    b = st.bundle
    vecs = [b.pack(_model(spec, 1 + i)) for i in range(min(W, UNIQUE_VECS))]
    updates = [vecs[i % len(vecs)] for i in range(W)]
    ws = [1.0 / (1 + (i % 3)) for i in range(W)]

    def step(server):
        return st.merge_rows(server, updates, ws, ALPHA)

    server = step(step(template))                 # warmup: trace + allocate
    jax.block_until_ready(jax.tree.leaves(server))
    t0 = time.perf_counter()
    for _ in range(rounds):
        server = step(server)
    jax.block_until_ready(jax.tree.leaves(server))
    ms = (time.perf_counter() - t0) / rounds * 1e3

    row_dev = max(st.row_bytes_by_device().values())
    srv_dev = max(s.data.nbytes for s in st._server_flat.addressable_shards)
    return {
        "model": name, "n_params": b.n_params, "W": W, "mesh": d,
        "merge_ms": round(ms, 3),
        "row_buffer_bytes_per_device": int(row_dev),
        "server_buffer_bytes_per_device": int(srv_dev),
        "row_buffer_bytes_total": int(W * b.padded_size * 4),
    }


def run(smoke: bool = False) -> dict:
    import jax
    import numpy as np

    from repro.core import flatbuf

    n_dev = jax.device_count()
    models = {"mlp_1m": MODELS["mlp_1m"]} if smoke else MODELS
    w_grid = (8,) if smoke else W_GRID
    rounds = 3 if smoke else ROUNDS
    cells, skipped = [], []
    for name, spec in models.items():
        n_params = sum(int(np.prod(s)) for s in spec.values())
        for W in w_grid:
            for d in MESH_GRID:
                if d > n_dev:
                    skipped.append({"model": name, "W": W, "mesh": d,
                                    "reason": f"only {n_dev} devices"})
                    continue
                full = W * flatbuf.padded_size_for(n_params, d) * 4
                if full > MEM_CAP:
                    skipped.append({"model": name, "W": W, "mesh": d,
                                    "reason": f"(W,N) buffer {full:.2e} B "
                                              f"> cap {MEM_CAP:.2e}"})
                    continue
                cells.append(_bench_cell(name, spec, W, d, rounds))
    rec = {
        "config": {"alpha": ALPHA, "rounds": rounds, "smoke": smoke,
                   "devices": n_dev, "mem_cap": MEM_CAP,
                   "backend": jax.default_backend()},
        "cells": cells,
        "skipped": skipped,
    }
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / "BENCH_agg_shard.json").write_text(json.dumps(rec, indent=2))
    return rec


def main() -> None:
    smoke = "--smoke" in sys.argv
    rec = run(smoke=smoke)
    print("== Sharded aggregation: merge ms / per-device live bytes "
          "vs mesh size ==")
    print(f"devices={rec['config']['devices']} "
          f"backend={rec['config']['backend']} smoke={smoke}")
    print("model,n_params,W,mesh,merge_ms,row_MB_per_device")
    for c in rec["cells"]:
        print(f"{c['model']},{c['n_params']},{c['W']},{c['mesh']},"
              f"{c['merge_ms']},"
              f"{c['row_buffer_bytes_per_device'] / 1e6:.2f}")
    for s in rec["skipped"]:
        print(f"skipped {s['model']} W={s['W']} mesh={s['mesh']}: "
              f"{s['reason']}")


if __name__ == "__main__":
    # standalone only (must precede the first jax import): CPU runs need
    # forced host devices for the >1 mesh cells.  Via run.py the session's
    # existing devices are used — other benchmarks' numbers must not be
    # skewed by a 4-virtual-device platform this module forced at import.
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=4")
    main()
