import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) cell
on the production meshes, record memory/cost/collective analysis.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--fl]

Results land in benchmarks/results/dryrun/<mesh>/<arch>__<shape>[__fl].json.
"""
import argparse
import functools
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro import optim
from repro.configs import SHAPES, get_config, list_archs
from repro.core import federated
from repro.launch import analytics, hlo_analysis, hlo_cost
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs, abstract_params, abstract_opt_state
from repro.models import prefill_step, serve_step, train_step
from repro.parallel import batch_specs, to_named_tree

RESULTS = Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"


def applicable(arch: str, shape: str) -> bool:
    cfg = get_config(arch)
    if shape == "long_500k" and not cfg.subquadratic:
        return False  # pure full-attention archs skip 500k decode (DESIGN.md §4)
    return True


def lower_cell(arch: str, shape: str, mesh, fl: bool = False,
               n_microbatch: int = 0):
    cfg = get_config(arch)
    n_microbatch = n_microbatch or cfg.microbatches
    optimizer = optim.adamw()
    kind, inputs = input_specs(arch, shape, mesh, optimizer)

    if kind == "train" and fl:
        n_pods = dict(zip(mesh.axis_names, mesh.devices.shape)).get("pod", 1)
        assert n_pods > 1, "--fl requires the multi-pod mesh"
        from jax.sharding import NamedSharding, PartitionSpec as P

        def podded(sds):
            spec = sds.sharding.spec
            return jax.ShapeDtypeStruct(
                (n_pods,) + sds.shape, sds.dtype,
                sharding=NamedSharding(mesh, P("pod", *spec)))
        sp = jax.tree.map(podded, inputs["params"])
        so = jax.tree.map(podded, inputs["opt_state"])
        step = functools.partial(federated.fl_local_step, cfg=cfg,
                                 optimizer=optimizer, n_pods=n_pods,
                                 n_microbatch=n_microbatch)
        fn = jax.jit(step, donate_argnums=(0, 1))
        lowered = fn.lower(sp, so, inputs["batch"])
        # the aggregation round (the paper's cross-pod weight exchange)
        wsds = jax.ShapeDtypeStruct((n_pods,), jnp.float32,
                                    sharding=NamedSharding(mesh, P()))
        round_fn = jax.jit(federated.fl_round, donate_argnums=(0,))
        lowered_round = round_fn.lower(sp, wsds)
        return [("fl_local_step", lowered), ("fl_round", lowered_round)]

    if kind == "train":
        from repro.parallel import param_specs
        import jax as _jax
        pshapes = _jax.eval_shape(
            functools.partial(__import__("repro.models", fromlist=["x"])
                              .init_params, cfg=cfg), _jax.random.PRNGKey(0))
        gspecs = param_specs(cfg, pshapes, mesh)
        step = functools.partial(train_step, cfg=cfg, optimizer=optimizer,
                                 n_microbatch=n_microbatch, grad_specs=gspecs)
        fn = jax.jit(step, donate_argnums=(0, 1))
        return [("train_step", fn.lower(inputs["params"], inputs["opt_state"],
                                        inputs["batch"]))]
    if kind == "prefill":
        step = functools.partial(prefill_step, cfg=cfg)
        fn = jax.jit(step)
        return [("prefill_step", fn.lower(inputs["params"], inputs["batch"]))]
    if kind == "decode":
        step = functools.partial(serve_step, cfg=cfg)
        b = inputs["batch"]
        if cfg.embeds_input:
            fn = jax.jit(lambda p, s, pos, e: step(p, s, None, pos, embeds=e),
                         donate_argnums=(1,))
            lowered = fn.lower(inputs["params"], inputs["state"],
                               inputs["cur_pos"], b["embeds"])
        else:
            fn = jax.jit(step, donate_argnums=(1,))
            lowered = fn.lower(inputs["params"], inputs["state"], b["tokens"],
                               inputs["cur_pos"])
        return [("serve_step", lowered)]
    raise ValueError(kind)


def run_cell(arch: str, shape: str, *, multi_pod: bool, fl: bool = False,
             save: bool = True, verbose: bool = True):
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    tag = f"{arch}__{shape}" + ("__fl" if fl else "")
    out_path = RESULTS / mesh_name / f"{tag}.json"
    if not applicable(arch, shape):
        rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
               "status": "skipped",
               "reason": "full-attention arch: long_500k requires "
                         "sub-quadratic attention (DESIGN.md §4)"}
        if save:
            out_path.parent.mkdir(parents=True, exist_ok=True)
            out_path.write_text(json.dumps(rec, indent=2))
        if verbose:
            print(f"[skip] {mesh_name}/{tag}")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name, "fl": fl,
           "status": "ok", "steps": {}}
    try:
        cfg = get_config(arch)
        rec["n_params"] = cfg.n_params()
        rec["n_active_params"] = cfg.n_active_params()
        rec["model_flops"] = analytics.model_flops(arch, shape)
        rec["n_microbatch"] = (cfg.microbatches
                               if SHAPES[shape]["kind"] == "train" else None)
        # set_mesh (context-manager form) exposes the abstract mesh to
        # trace-time sharding constraints (sequence parallelism etc.)
        with jax.sharding.set_mesh(mesh):
            lowered_steps = lower_cell(arch, shape, mesh, fl=fl)
        for name, lowered in lowered_steps:
            t1 = time.time()
            compiled = lowered.compile()
            cost = compiled.cost_analysis()
            mem = hlo_analysis.memory_summary(compiled)
            parsed = hlo_cost.analyze(compiled.as_text())
            terms = hlo_analysis.roofline_terms(parsed, cost)
            rec["steps"][name] = {
                "compile_s": round(time.time() - t1, 2),
                "memory": mem,
                "roofline": terms,
            }
            if verbose:
                pk = mem.get("peak_estimate_bytes", 0) / 2**30
                print(f"[ok] {mesh_name}/{tag}:{name} "
                      f"compile={rec['steps'][name]['compile_s']}s "
                      f"peak/dev={pk:.2f}GiB dom={terms['dominant']} "
                      f"tc={terms['t_compute_s']:.4f} tm={terms['t_memory_s']:.4f} "
                      f"tx={terms['t_collective_s']:.4f}")
    except Exception as e:  # noqa: BLE001 — record failures, they are bugs
        rec["status"] = "error"
        rec["error"] = f"{e.__class__.__name__}: {e}"
        rec["traceback"] = traceback.format_exc(limit=20)
        if verbose:
            print(f"[FAIL] {mesh_name}/{tag}: {rec['error']}")
    rec["total_s"] = round(time.time() - t0, 2)
    if save:
        out_path.parent.mkdir(parents=True, exist_ok=True)
        out_path.write_text(json.dumps(rec, indent=2))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--fl", action="store_true",
                    help="lower the federated local-step + aggregation round "
                         "(train shapes, multi-pod)")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    n_fail = 0
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                if args.fl and (SHAPES[shape]["kind"] != "train" or not mp):
                    continue
                rec = run_cell(arch, shape, multi_pod=mp, fl=args.fl)
                if rec["status"] == "error":
                    n_fail += 1
    print(f"done; failures={n_fail}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
