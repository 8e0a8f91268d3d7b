"""Benchmark entry point: one run of one cell on the chip.

    python3 bench/run.py --workload mnist-xdev-sync-raw --seed 7 \\
        --seconds 10 --trace 0

Runs from the root of a checkout.  The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
the compared numbers beside their limits (``checks``), which also end
standard error.  Off a TPU, or with fewer chips than the cell asks for,
it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def main(argv=None, **harness_kw) -> None:
    """``harness_kw`` goes to ``harness.run``: tests give a gate that
    takes the CPU, and sizes a CPU holds."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import device, harness
    harness_kw.setdefault("gate", device.device_gate)
    res = harness.run(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=T_START, **harness_kw)
    print(res.line(), flush=True)


if __name__ == "__main__":
    main()
