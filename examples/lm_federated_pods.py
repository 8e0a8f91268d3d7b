"""End-to-end driver: pretrain a small LM with the paper's technique at the
pod level — local-SGD on each (simulated) pod, worker-selection-weighted
cross-pod aggregation every H steps, checkpoint/restart.

This is the LM-scale face of the FL engine: the same `fl_local_step` /
`fl_round` pair that the 512-chip dry-run lowers for the production mesh
(see benchmarks/results/dryrun/multipod_2x16x16/*__fl.json), running here on
CPU with a reduced config so a few hundred steps finish in minutes.

    PYTHONPATH=src python examples/lm_federated_pods.py --steps 120
"""
import argparse
import functools
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp

from repro import optim
from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core import federated
from repro.data import synthetic_token_batches
from repro.models import init_params
from repro.runtime.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--pods", type=int, default=2)
    ap.add_argument("--fl-every", type=int, default=10)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_fl_lm")
    args = ap.parse_args()

    cfg = get_config("yi-9b", reduced=True).replace(
        name="yi-mini", n_layers=4, d_model=128, n_heads=8, n_kv_heads=4,
        d_ff=384, vocab_size=2048, loss_chunk=32)
    optimizer = optim.adamw(1e-3)
    params = init_params(jax.random.PRNGKey(0), cfg)
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {n/1e6:.1f}M params x {args.pods} pod workers, "
          f"aggregating every {args.fl_every} steps")

    sp = federated.stack_for_pods(params, args.pods)
    so = federated.stack_for_pods(optimizer.init(params), args.pods)
    local = jax.jit(functools.partial(federated.fl_local_step, cfg=cfg,
                                      optimizer=optimizer, n_pods=args.pods))
    rnd = jax.jit(federated.fl_round)
    mgr = CheckpointManager(args.ckpt_dir)
    data = synthetic_token_batches(vocab=cfg.vocab_size,
                                   batch=args.batch * args.pods,
                                   seq_len=args.seq, seed=0)
    t0 = time.time()
    for step in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(data).items()}
        sp, so, m = local(sp, so, batch)
        if (step + 1) % args.fl_every == 0:
            # simple selection: all pods healthy -> equal weights
            sp = rnd(sp, jnp.ones((args.pods,), jnp.float32))
        if step % 10 == 0 or step == args.steps - 1:
            losses = [f"{float(l):.3f}" for l in m["loss"]]
            print(f"step {step:4d} per-pod loss {losses} "
                  f"({time.time()-t0:.0f}s)")
        if (step + 1) % 50 == 0:
            mgr.save(step + 1, {"params": sp, "opt": so})
    print(f"done in {time.time()-t0:.0f}s; checkpoints: {mgr.steps()}")


if __name__ == "__main__":
    main()
