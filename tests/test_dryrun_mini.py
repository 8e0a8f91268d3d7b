"""Mini dry-run: exercise the lower+compile+analysis machinery on a small
fake-device mesh with reduced configs (the 512-device production sweep runs
via ``python -m repro.launch.dryrun --all``; results in benchmarks/results)."""
import functools
import json
from pathlib import Path

import pytest

RESULTS = Path(__file__).resolve().parents[1] / "benchmarks" / "results" / "dryrun"


def test_hlo_cost_counts_scan_trip_counts():
    import jax
    import jax.numpy as jnp
    from repro.launch import hlo_cost

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y.sum()

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    compiled = jax.jit(f).lower(x, w).compile()
    res = hlo_cost.analyze(compiled.as_text())
    expect = 10 * 2 * 64 * 128 * 128
    assert abs(res["flops"] - expect) / expect < 0.05
    # XLA's own analysis undercounts the while body — ours must not
    xla_cost = compiled.cost_analysis()
    assert res["flops"] > 5 * float(xla_cost["flops"])


@pytest.mark.parametrize("mesh_dir", ["pod_16x16", "multipod_2x16x16"])
def test_production_dryrun_results_complete(mesh_dir):
    """Every (arch x shape) cell has a result file and none errored.
    (Generated by the production sweep; this asserts its invariants.)"""
    d = RESULTS / mesh_dir
    if not d.exists():
        pytest.skip("production dry-run sweep has not been run")
    recs = [json.loads(p.read_text()) for p in d.glob("*.json")
            if "__fl" not in p.name]
    assert len(recs) == 40, f"expected 40 cells, got {len(recs)}"
    errors = [r for r in recs if r["status"] == "error"]
    assert not errors, [e["arch"] + "/" + e["shape"] for e in errors]
    skips = [r for r in recs if r["status"] == "skipped"]
    # exactly the documented long_500k skips for full-attention archs
    assert sorted(s["arch"] for s in skips) == sorted([
        "gemma2-2b", "yi-9b", "deepseek-67b", "starcoder2-15b",
        "phi3.5-moe-42b-a6.6b", "internvl2-26b", "musicgen-medium"])


def test_fl_variant_results_exist():
    d = RESULTS / "multipod_2x16x16"
    if not d.exists():
        pytest.skip("production dry-run sweep has not been run")
    fl = [json.loads(p.read_text()) for p in d.glob("*__fl.json")]
    assert len(fl) == 10
    for r in fl:
        assert r["status"] == "ok"
        assert "fl_local_step" in r["steps"] and "fl_round" in r["steps"]
        # the federated local step must not move more collective bytes than
        # an entire sync step would — the pod axis is silent
        local = r["steps"]["fl_local_step"]["roofline"]
        assert local["collective_wire_bytes_per_device"] > 0
