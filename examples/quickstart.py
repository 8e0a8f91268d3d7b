"""Quickstart: federated learning with worker selection in ~30 seconds.

Builds the thesis' 10-worker setup (even data split, heterogeneous worker
profiles), runs synchronous FL with the training-time-based selector
(Algorithm 2), and prints accuracy over simulated time.

    PYTHONPATH=src python examples/quickstart.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.core import TABLE_4_1, make_setup, run_fl, time_to_accuracy
from repro.runtime.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    setup = make_setup(TABLE_4_1["mnist_even"], seed=0, noise=0.2,
                       batch_size=64, het="extreme")
    print(f"10 workers, {setup.model_bytes/1e3:.0f} KB model, "
          f"profiles: {[round(p.cpu_freq * p.cpu_prop, 2) for p in setup.profiles]}"
          " effective GHz")
    history = run_fl(setup, mode="sync", selector="time_based",
                     epochs_per_round=10, max_rounds=120,
                     selector_kw={"r": 10, "T0": 0.0, "A": 0.01})
    print(f"\n{'sim time':>9} {'round':>6} {'accuracy':>9} {'#updates':>9}")
    for p in history[::6]:
        print(f"{p.time:>9.2f} {p.version:>6} {p.accuracy:>9.3f} "
              f"{p.n_updates:>9}")
    t80 = time_to_accuracy(history, 0.8)
    print(f"\nreached 80% accuracy at simulated t={t80:.2f}s "
          f"(final {history[-1].accuracy:.3f})")


if __name__ == "__main__":
    main()
