# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows (derived = the figure's headline metric), then the roofline and
# FL-collective tables from the dry-run artifacts.
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
# the `benchmarks` package itself (namespace pkg, no __init__.py): direct
# `python benchmarks/run.py` invocations need the repo root importable too
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    from benchmarks import agg_bench, agg_shard_bench, fl_figures, \
        roofline, scale_bench, wire_bench
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()

    # CI smoke dispatch: run exactly one tiny sweep and exit (the full
    # table below is the local/nightly path).  One entry point per flag:
    # --smoke-dlink lives in fl_figures.py's __main__; --smoke-topology,
    # --smoke-chaos, --smoke-scale and --smoke-autotune here
    if "--smoke-topology" in sys.argv:
        print(json.dumps(fl_figures.fig_topology_sweep(smoke=True),
                         indent=2))
        return
    if "--smoke-chaos" in sys.argv:
        print(json.dumps(fl_figures.fig_chaos_sweep(smoke=True),
                         indent=2))
        return
    if "--smoke-scale" in sys.argv:
        scale_bench.main(smoke=True)
        return
    if "--smoke-autotune" in sys.argv:
        print(json.dumps(fl_figures.fig_autotune_sweep(smoke=True),
                         indent=2))
        return
    if "--smoke-resume" in sys.argv:
        print(json.dumps(fl_figures.fig_resume_sweep(smoke=True),
                         indent=2))
        return
    if "--smoke-hetero" in sys.argv:
        print(json.dumps(fl_figures.fig_heterogeneity_sweep(smoke=True),
                         indent=2))
        return

    # a benchmark that raises fails the whole run, naming itself
    for bench in (agg_bench.main, agg_shard_bench.main, wire_bench.main,
                  scale_bench.main):
        try:
            bench()
        except Exception as e:
            raise RuntimeError(f"benchmark {bench.__module__} failed") from e
        print()

    print("name,us_per_call,derived")
    for name, fn in fl_figures.ALL.items():
        t0 = time.time()
        try:
            derived = fn()
        except Exception as e:
            raise RuntimeError(f"benchmark {name} failed") from e
        us = (time.time() - t0) * 1e6
        short = json.dumps(derived, default=lambda o: round(o, 3)
                           if isinstance(o, float) else o)
        short = short.replace(",", ";")
        print(f"{name},{us:.0f},{short}")

    print()
    print("== Roofline (single pod, per-device seconds per step) ==")
    print(roofline.table("pod_16x16"))
    print()
    print("== Multi-pod (512 chips) ==")
    print(roofline.table("multipod_2x16x16"))
    print()
    print("== Paper technique at pod scale: sync-DP vs federated local-SGD ==")
    print(roofline.fl_comparison())


if __name__ == '__main__':
    main()
