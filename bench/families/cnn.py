"""The thesis CNN family (arXiv:2211.07238, §4.2.4, Listing 4.1):
conv5x5 -> ReLU -> maxpool2 -> conv5x5 -> ReLU -> maxpool2 -> dense.

Three things live here, all the benchmark's own:

* the set-up: the weights made from the seed in one jitted call on the
  device, and the program's ``FLSetup`` around the program's own
  ``train_fn`` (``cnn_sgd_train`` through ``cnn_train_wrapper``) and
  ``eval_fn`` (``cnn_accuracy``);
* the operation and byte counts of local training, from the shapes;
* the plain reference: forward pass, loss, full-batch SGD and accuracy in
  straightforward ``jax.numpy`` at float32 and ``HIGHEST`` precision,
  importing nothing of the program, and the same at bfloat16 as the
  control that ``correct`` has to reject.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

KERNEL = 5       # conv window, both layers (Listing 4.1)
POOL = 2
F32 = jnp.float32


# --- shapes and counts --------------------------------------------------------

def layer_flops(cfg: dict) -> dict:
    """Multiply-add operations (2 per MAC) of one image's forward pass, by
    layer; bias, ReLU and pooling are not counted."""
    hw, c, c1, c2 = (cfg["image_hw"], cfg["channels"], cfg["conv1"],
                     cfg["conv2"])
    flat = (hw // 4) * (hw // 4) * c2
    return {"conv1": 2 * hw * hw * c1 * KERNEL * KERNEL * c,
            "conv2": 2 * (hw // 2) ** 2 * c2 * KERNEL * KERNEL * c1,
            "dense": 2 * flat * cfg["n_classes"]}


def forward_flops(cfg: dict) -> int:
    return int(sum(layer_flops(cfg).values()))


def train_flops(cfg: dict, n_images: int, epochs: int) -> int:
    """Operations that full-batch SGD needs: per image and epoch the
    forward pass, the weight gradients of every layer (as many again) and
    the input gradients of every layer but the first (the image needs
    none)."""
    per = layer_flops(cfg)
    per_image = 3 * sum(per.values()) - per["conv1"]
    return int(per_image * n_images * epochs)


def n_params(cfg: dict) -> int:
    c, c1, c2 = cfg["channels"], cfg["conv1"], cfg["conv2"]
    flat = (cfg["image_hw"] // 4) ** 2 * c2
    return (KERNEL * KERNEL * c * c1 + c1 + KERNEL * KERNEL * c1 * c2 + c2
            + flat * cfg["n_classes"] + cfg["n_classes"])


def train_bytes(cfg: dict, n_images: int, epochs: int) -> int:
    """Least HBM traffic of one training call: the images and labels read
    once, the f32 parameters read and written once."""
    hw, c = cfg["image_hw"], cfg["channels"]
    return int(n_images * (hw * hw * c * 4 + 4) + 2 * 4 * n_params(cfg))


# --- set-up -------------------------------------------------------------------

def _he(key, shape, fan_in):
    return jax.random.normal(key, shape, F32) * jnp.sqrt(2.0 / fan_in)


@functools.partial(jax.jit, static_argnames=("hw", "c", "c1", "c2", "k"))
def _init(key, hw, c, c1, c2, k):
    ks = jax.random.split(key, 3)
    flat = (hw // 4) * (hw // 4) * c2
    return {"c1w": _he(ks[0], (KERNEL, KERNEL, c, c1), KERNEL * KERNEL * c),
            "c1b": jnp.zeros((c1,), F32),
            "c2w": _he(ks[1], (KERNEL, KERNEL, c1, c2), KERNEL * KERNEL * c1),
            "c2b": jnp.zeros((c2,), F32),
            "fw": _he(ks[2], (flat, k), flat),
            "fb": jnp.zeros((k,), F32)}


def init_weights(key, cfg: dict) -> dict:
    """He-normal kernels and zero biases, made on the device in one jitted
    call from ``key``: the weights both the program and the reference
    start from."""
    return _init(key, cfg["image_hw"], cfg["channels"], cfg["conv1"],
                 cfg["conv2"], cfg["n_classes"])


def program_eval_fn(test_x: np.ndarray, test_y: np.ndarray):
    """The program's evaluation over a test set: ``cnn_accuracy`` and the
    ``float`` that reads it back, as ``run_fl``'s set-up builds it."""
    from repro.models import cnn as program_cnn
    tx, ty = jnp.asarray(test_x), jnp.asarray(test_y)
    return lambda w: float(program_cnn.cnn_accuracy(w, tx, ty))


def build_setup(cfg: dict, traffic, weights0):
    """The program's ``FLSetup`` over the traffic's shards and test set,
    with the program's own training and evaluation functions."""
    from repro.configs.paper_cnn import CNNConfig
    from repro.core.experiment import FLSetup, cnn_train_wrapper

    if cfg["local_optimizer"] != "sgd":
        raise ValueError("the program trains CNN workers with full-batch "
                         f"SGD only, not {cfg['local_optimizer']!r}")
    ccfg = CNNConfig(name=cfg["name"], image_hw=cfg["image_hw"],
                     channels=cfg["channels"], conv1=cfg["conv1"],
                     conv2=cfg["conv2"], n_classes=cfg["n_classes"],
                     lr=cfg["lr"])
    model_bytes = int(sum(leaf.size * leaf.dtype.itemsize
                          for leaf in jax.tree.leaves(weights0)))
    return FLSetup(
        cfg=ccfg, weights0=weights0, shards=traffic.shards,
        profiles=traffic.profiles, test_x=traffic.test_x,
        test_y=traffic.test_y, model_bytes=model_bytes,
        train_fn=functools.partial(cnn_train_wrapper, lr=ccfg.lr),
        eval_fn=program_eval_fn(traffic.test_x, traffic.test_y),
        per_batch_server=float(traffic.spec["per_batch_server"]))


# --- plain reference ----------------------------------------------------------

def _conv(x, w, b, precision):
    """'SAME' stride-1 convolution as one matrix product over the image's
    KERNEL x KERNEL patches (NHWC images, HWIO kernel)."""
    n, h, wd, c = x.shape
    pad = KERNEL // 2
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    patches = jnp.concatenate([xp[:, i:i + h, j:j + wd, :]
                               for i in range(KERNEL) for j in range(KERNEL)],
                              axis=-1)
    y = jnp.dot(patches.reshape(n * h * wd, KERNEL * KERNEL * c),
                w.reshape(KERNEL * KERNEL * c, -1), precision=precision)
    return y.reshape(n, h, wd, -1) + b


def _pool(x):
    n, h, w, c = x.shape
    return x.reshape(n, h // POOL, POOL, w // POOL, POOL, c).max(axis=(2, 4))


def logits(params, x, precision=jax.lax.Precision.HIGHEST):
    h = _pool(jax.nn.relu(_conv(x, params["c1w"], params["c1b"], precision)))
    h = _pool(jax.nn.relu(_conv(h, params["c2w"], params["c2b"], precision)))
    h = h.reshape(h.shape[0], -1)
    return jnp.dot(h, params["fw"], precision=precision) + params["fb"]


def loss(params, x, y, precision=jax.lax.Precision.HIGHEST):
    lg = logits(params, x, precision).astype(F32)
    gold = jnp.take_along_axis(lg, y[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - gold)


@functools.partial(jax.jit, static_argnames=("lr", "epochs", "dtype"))
def train(params, x, y, lr: float, epochs: int, dtype=F32):
    """``epochs`` steps of full-batch SGD at learning rate ``lr``.  At
    float32 every product runs at ``HIGHEST`` precision; at bfloat16
    (the control) the parameters, the images and every product are
    bfloat16."""
    precision = (jax.lax.Precision.HIGHEST if dtype == F32
                 else jax.lax.Precision.DEFAULT)
    xs = x.astype(dtype)

    def step(_, p):
        g = jax.grad(loss)(p, xs, y, precision)
        return jax.tree.map(lambda a, b: (a - lr * b).astype(dtype), p, g)

    p = jax.lax.fori_loop(0, epochs, step,
                          jax.tree.map(lambda a: a.astype(dtype), params))
    return jax.tree.map(lambda a: a.astype(F32), p)


EVAL_BLOCK = 1000    # test images the reference scores at a time


@jax.jit
def _hits(params, x, y):
    return jnp.sum(jnp.argmax(logits(params, x), axis=-1) == y)


def control_train_fn(cfg: dict):
    """The reference's training at bfloat16, with the program's
    ``train_fn`` signature: the control put in the program's place."""
    def fn(params, x, y, epochs):
        return train(params, jnp.asarray(x), jnp.asarray(y),
                     lr=float(cfg["lr"]), epochs=int(epochs),
                     dtype=jnp.bfloat16)
    return fn


def ref_train(cfg: dict, params, x: np.ndarray, y: np.ndarray, epochs: int):
    return train(params, jnp.asarray(x), jnp.asarray(y),
                 lr=float(cfg["lr"]), epochs=int(epochs))


def ref_accuracy(params, x: np.ndarray, y: np.ndarray) -> float:
    """Share of the test set classified right, scored in blocks of
    ``EVAL_BLOCK`` images so that the patch products fit."""
    hits = sum(int(_hits(params, jnp.asarray(x[i:i + EVAL_BLOCK]),
                         jnp.asarray(y[i:i + EVAL_BLOCK])))
               for i in range(0, len(x), EVAL_BLOCK))
    return hits / len(x)
