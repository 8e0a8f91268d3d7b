"""Readings for the limits of ``correct``: the compared numbers of the
program, of the control, and of planted faults, over many seeds in one
process.  Not part of a benchmark run.

    python3 bench/calibrate.py --workloads mnist-t42-async-raw \\
        --modes program control --seeds 101 102 103

Modes: ``program`` (the system as the benchmark runs it), ``control``
(the reference's training at bfloat16 in the program's place), ``half``
(the program training on half of each worker's batch), ``frozen`` (a
training that returns its input unchanged), ``stale_eval`` (evaluation
scores the version before the one it is given), ``half_test``
(evaluation on half of the test set), ``merge_half`` (the merge leaves
out the second half of its rows and takes the mean over the rest).  Each
seed drives the federation through the cell's compared first versions,
as a run's set-up does, and prints one JSON line of its numbers.  Runs
on the chip, like ``run.py``; ``--fault-seeds`` (default: the first three
seeds) serve every mode but ``program``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def _frozen(cell):
    return lambda setup, tr: {"train_fn": lambda params, x, y, epochs: params}


def _half(cell):
    def plant(setup, tr):
        prog = setup.train_fn

        def half(params, x, y, epochs):
            n = max(1, len(x) // 2)
            return prog(params, x[:n], y[:n], epochs)
        return {"train_fn": half}
    return plant


def _control(cell):
    return lambda setup, tr: {
        "train_fn": cell.family.control_train_fn(cell.config)}


def _stale_eval(cell):
    def plant(setup, tr):
        prog, held = setup.eval_fn, []

        def stale(weights):
            held.append(weights)
            return prog(held.pop(0) if len(held) > 1 else weights)
        return {"eval_fn": stale}
    return plant


def _half_test(cell):
    def plant(setup, tr):
        n = len(tr.test_x) // 2
        return {"eval_fn": cell.family.program_eval_fn(tr.test_x[:n],
                                                       tr.test_y[:n])}
    return plant


# mode -> the plant (see harness.build) it puts under the timed path
PLANTS = {"program": lambda cell: None, "control": _control, "half": _half,
          "frozen": _frozen, "stale_eval": _stale_eval,
          "half_test": _half_test, "merge_half": lambda cell: None}
MODES = tuple(PLANTS)


@contextlib.contextmanager
def merge_half_rows():
    """While open, every merge of the program keeps only the first half
    of its rows (at least one), weighted as before and renormalised."""
    from repro.core import flatbuf
    program = flatbuf.normalized_weights

    def half(weights):
        w = np.asarray(weights, np.float64).copy()
        w[max(1, (len(w) + 1) // 2):] = 0.0
        return program(w)
    flatbuf.normalized_weights = half
    try:
        yield
    finally:
        flatbuf.normalized_weights = program


def planted(mode: str, cell):
    """``(plant, context)`` of ``mode``: what goes in the place of the
    program's training or evaluation, and what is patched while it runs."""
    ctx = merge_half_rows() if mode == "merge_half" else contextlib.nullcontext()
    return PLANTS[mode](cell), ctx


def readings(workload: str, seed: int, mode: str, log) -> dict:
    """The compared numbers of one seed under ``mode``."""
    from bench import harness
    cell = harness.load_cell(workload)
    plant, ctx = planted(mode, cell)
    with ctx:
        b, cap = harness.prepare(cell, seed, log, plant)
    tr = b.traffic
    del b
    return harness.reference_numbers(cell, cap, tr)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workloads", nargs="+", required=True)
    ap.add_argument("--modes", choices=MODES, nargs="+", default=["program"])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault-seeds", type=int, nargs="+")
    args = ap.parse_args(argv)
    from bench import device
    from repro.runtime.compile_cache import enable_compile_cache
    dev = device.device_gate(1)
    enable_compile_cache()
    log = device.CompileLog()
    for workload in args.workloads:
        for mode in args.modes:
            seeds = (args.seeds if mode == "program"
                     else args.fault_seeds or args.seeds[:3])
            for seed in seeds:
                try:
                    nums = readings(workload, seed, mode, log)
                except Exception as e:       # a fault that crashes has failed
                    nums = {"error": f"{type(e).__name__}: {e}"[:300]}
                print(json.dumps({"workload": workload, "mode": mode,
                                  "seed": seed, **nums,
                                  "device": dev["kind"]}), flush=True)


if __name__ == "__main__":
    main()
