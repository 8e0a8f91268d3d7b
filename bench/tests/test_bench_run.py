"""A whole run of the harness on the CPU, at a size a CPU holds: it finds
the cell's files by name, prints the result line, refuses a run off the
chip, and its ``correct`` rejects the control and each planted fault."""
import contextlib
import io
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import calibrate, device, harness, run

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "mnist-xdev-sync-raw"
# the cell's shapes cut to what a test holds: 40 workers of one 32-image
# batch, a cohort of 8, 8x8 images, two epochs
TINY = {"config": {"image_hw": 8, "conv1": 4, "conv2": 8},
        "traffic": {"n_test": 64, "workers": 40, "batches_each": 1,
                    "batch_size": 32},
        "run": {"epochs_per_round": 2, "cohort": 8}}
# the same at a rate at which accuracy moves from version to version, for
# the faults of evaluation
LEARNS = {**TINY, "config": {**TINY["config"], "lr": 0.1},
          "run": {**TINY["run"], "epochs_per_round": 5}}


def cpu_gate(chips):
    return device.describe(chips)


def test_every_cell_finds_its_files():
    for w in SPEC["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.limits and cell.family.init_weights
        for m in cell.per_layer:
            reader = harness.load_module(
                ROOT / "bench" / "metrics" / f"{m['name']}.py")
            assert callable(reader.read)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}


def test_rehearsal_prints_the_result_line():
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        run.main(["--workload", CELL, "--seed", str(2 ** 31 + 7),
                  "--seconds", "0.3", "--trace", "0"],
                 gate=cpu_gate, overrides=TINY, compile_cache=False)
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {m["name"] for m in SPEC["end_to_end"]
                                    if harness.applies(m, CELL)}
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())


def test_off_the_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", CELL,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout


def _altered(cell):
    def plant(setup, tr):
        prog = setup.train_fn

        def fn(params, x, y, epochs):
            out = prog(params, x, y, epochs)
            return dict(out, c1w=out["c1w"].at[0, 0, 0, 0].add(0.5))
        return {"train_fn": fn}
    return plant


# each fault a cell of this kind can have, planted under the timed path:
# (calibrate's mode, or None for an altered answer; the sizes)
FAULTS = {"state_unchanged": ("frozen", TINY), "half_batch": ("half", TINY),
          "control_bf16": ("control", TINY),
          "stale_eval": ("stale_eval", LEARNS),
          "half_test_set": ("half_test", LEARNS),
          "merge_half_rows": ("merge_half", TINY),
          "answer_altered": (None, TINY)}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_correct_rejects(fault):
    mode, sizes = FAULTS[fault]
    cell = harness.load_cell(CELL, sizes)
    if mode is None:
        plant, ctx = _altered(cell), contextlib.nullcontext()
    else:
        plant, ctx = calibrate.planted(mode, cell)
    with ctx:
        res = harness.run(CELL, 11, 0.3, False, t_start=0.0, gate=cpu_gate,
                          overrides=sizes, compile_cache=False, plant=plant)
    assert res.correct is False, res.checks
