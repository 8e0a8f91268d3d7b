"""A toy model family for the harness's tests: token ids in, a nested
parameter tree (``{"embed": {"table"}, "head": {"w", "b"}}``), plain
full-batch SGD.  The mean of a sequence's token embeddings goes through a
dense head; the label is the first token's id modulo ``n_classes``.

It gives what the harness asks of a family: ``dataset``,
``init_weights``, ``build_setup`` around its own training (standing for
the program's), ``ref_train`` and ``ref_accuracy`` (the plain reference,
at ``HIGHEST`` precision), ``n_params`` and ``TRAIN_PROGRAMS``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.traffic import seed_words

F32 = jnp.float32
TRAIN_PROGRAMS = ("jit__program_sgd",)
HIGHEST = jax.lax.Precision.HIGHEST


def dataset(n: int, cfg: dict, spec: dict, seed: int):
    """``n`` sequences of ``seq_len`` token ids and their labels."""
    rng = np.random.RandomState(seed_words(seed))
    x = rng.randint(0, cfg["vocab"], size=(n, cfg["seq_len"])).astype(np.int32)
    return x, (x[:, 0] % cfg["n_classes"]).astype(np.int32)


def n_params(cfg: dict) -> int:
    v, d, c = cfg["vocab"], cfg["dim"], cfg["n_classes"]
    return v * d + d * c + c


@functools.partial(jax.jit, static_argnames=("v", "d", "c"))
def _init(key, v, d, c):
    k1, k2 = jax.random.split(key)
    return {"embed": {"table": jax.random.normal(k1, (v, d), F32)},
            "head": {"w": jax.random.normal(k2, (d, c), F32) / np.sqrt(d),
                     "b": jnp.zeros((c,), F32)}}


def init_weights(key, cfg: dict):
    return _init(key, cfg["vocab"], cfg["dim"], cfg["n_classes"])


def logits(params, x, precision=None):
    h = jnp.mean(params["embed"]["table"][x], axis=1)
    return (jnp.dot(h, params["head"]["w"], precision=precision)
            + params["head"]["b"])


def loss(params, x, y, precision=None):
    lg = logits(params, x, precision)
    gold = jnp.take_along_axis(lg, y[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - gold)


def _sgd(params, x, y, lr, epochs, precision):
    def step(_, p):
        g = jax.grad(loss)(p, x, y, precision)
        return jax.tree.map(lambda a, b: a - lr * b, p, g)
    return jax.lax.fori_loop(0, epochs, step, params)


@functools.partial(jax.jit, static_argnames=("lr", "epochs"))
def _program_sgd(params, x, y, lr, epochs):
    return _sgd(params, x, y, lr, epochs, None)


@functools.partial(jax.jit, static_argnames=("lr", "epochs"))
def _ref_sgd(params, x, y, lr, epochs):
    return _sgd(params, x, y, lr, epochs, HIGHEST)


@jax.jit
def _hits(params, x, y):
    return jnp.sum(jnp.argmax(logits(params, x, HIGHEST), axis=-1) == y)


def build_setup(cfg: dict, traffic, weights0):
    from repro.core.experiment import FLSetup
    lr = float(cfg["lr"])
    tx, ty = jnp.asarray(traffic.test_x), jnp.asarray(traffic.test_y)
    return FLSetup(
        cfg=cfg, weights0=weights0, shards=traffic.shards,
        profiles=traffic.profiles, test_x=traffic.test_x,
        test_y=traffic.test_y, model_bytes=4 * n_params(cfg),
        train_fn=lambda p, x, y, epochs: _program_sgd(
            p, jnp.asarray(x), jnp.asarray(y), lr=lr, epochs=int(epochs)),
        eval_fn=lambda w: float(_hits(w, tx, ty)) / len(traffic.test_y),
        per_batch_server=float(traffic.spec["per_batch_server"]))


def ref_train(cfg: dict, params, x: np.ndarray, y: np.ndarray, epochs: int):
    return _ref_sgd(params, jnp.asarray(x), jnp.asarray(y),
                    lr=float(cfg["lr"]), epochs=int(epochs))


def ref_accuracy(params, x: np.ndarray, y: np.ndarray) -> float:
    return int(_hits(params, jnp.asarray(x), jnp.asarray(y))) / len(x)
