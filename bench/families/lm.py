"""The language-model family: one expert-parallel share of a decoder-only
mixture-of-experts transformer, as the configuration's Hugging Face keys
describe it (``bench/configs/mellum2-12b-a2.5b-ep8.json``): a period of
sliding-window and full layers, each with its own RoPE (YaRN on the full
ones), a router over all the experts of which this chip holds a slice, and
an untied slice of the vocabulary.

Four things live here, all the benchmark's own:

* the data: sequences from a seeded first-order Markov chain over the
  vocabulary slice, each id with a few Zipf-weighted successors, so that
  next-token accuracy can move (``dataset``);
* the set-up: f32 weights made from the seed in one jitted call on the
  device, and the program's ``FLSetup`` around the program's own
  ``train_fn`` (``lm_sgd_train`` through ``lm_train_wrapper``) and
  ``eval_fn`` (``lm_accuracy``);
* the operation and byte counts of local training, from the shapes;
* the plain reference: forward pass, loss, full-batch SGD and accuracy in
  straightforward ``jax.numpy`` at float32 and ``HIGHEST`` precision,
  importing nothing of the program, and the same with bfloat16 weights as
  the control that ``correct`` has to reject.  Attention runs per sequence
  and by query blocks with explicit masks; the held experts run as dense
  products over every token, masked by the gates; gradients are summed
  sequence by sequence.

The parameter tree is the program's: ``embed``/``lm_head``
(``{"embedding": (V, D)}``), ``final_norm``, and ``blocks`` stacked
(periods, layers of a period, ...), RMSNorm scales as ``1 + scale``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.traffic import seed_words
from repro.configs.base import ModelConfig
from repro.core.experiment import FLSetup, lm_accuracy, lm_train_wrapper

F32 = jnp.float32
# the program's local-training programs, by name in the device trace
TRAIN_PROGRAMS = ("jit_lm_sgd_train",)
SUCCESSORS = 4         # next ids each id of the chain may go to
QUERY_BLOCK = 1024     # the reference's attention, queries at a time
# the configuration ``init_weights`` last built, for the functions the
# harness calls without one (``ref_accuracy``, ``program_eval_fn``)
_built: dict = {}


# --- the configuration's keys ------------------------------------------------

def windows(cfg: dict) -> tuple:
    """Each layer's attention window, 0 for a full layer."""
    return tuple(cfg["sliding_window"] if t == "sliding_attention" else 0
                 for t in cfg["layer_types"][:cfg["num_hidden_layers"]])


def rope(cfg: dict, window: int) -> dict:
    return cfg["rope_parameters"][
        "sliding_attention" if window else "full_attention"]


def held(cfg: dict) -> int:
    return int(cfg["experts_held"])


# --- data ---------------------------------------------------------------------

def dataset(n: int, cfg: dict, spec: dict, seed: int):
    """``n`` sequences of ``seq_len`` token ids and their next ids: a
    random walk on a seeded graph in which each id has ``SUCCESSORS``
    successors, taken with weights 1, 1/2, 1/3, ... ."""
    rng = np.random.RandomState(seed_words(seed))
    v, length = int(cfg["vocab_size"]), int(cfg["seq_len"])
    succ = rng.randint(0, v, size=(v, SUCCESSORS))
    w = 1.0 / np.arange(1, SUCCESSORS + 1)
    cdf = np.cumsum(w / w.sum())
    pick = np.minimum(np.searchsorted(cdf, rng.random_sample((n, length)),
                                      side="right"), SUCCESSORS - 1)
    seq = np.empty((n, length + 1), np.int32)
    seq[:, 0] = rng.randint(0, v, size=n)
    for t in range(length):
        seq[:, t + 1] = succ[seq[:, t], pick[:, t]]
    return (np.ascontiguousarray(seq[:, :-1]),
            np.ascontiguousarray(seq[:, 1:]))


# --- shapes and counts --------------------------------------------------------

def _dims(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["num_experts"])


def n_params(cfg: dict) -> int:
    d, h, kv, hd, f, e = _dims(cfg)
    per_layer = (2 * d * h * hd + 2 * d * kv * hd       # q, o; k, v
                 + 3 * held(cfg) * d * f + d * e + 2 * d)
    heads = 1 if cfg["tie_word_embeddings"] else 2
    return (cfg["num_hidden_layers"] * per_layer
            + heads * cfg["vocab_size"] * d + d)


def mean_keys(length: int, window: int) -> float:
    """Keys a query attends to, averaged over a causal sequence."""
    w = window or length
    return (w * (w + 1) / 2 + (length - w) * w) / length


def forward_flops_per_token(cfg: dict) -> float:
    """Multiply-add operations (2 per MAC) of one token's forward pass:
    projections, the router, the held experts at the rows routed to them
    on average (``top_k * held / experts`` a token), scores and the
    weighted sum of values over the keys a query sees, and the head."""
    d, h, kv, hd, f, e = _dims(cfg)
    k = cfg["num_experts_per_tok"]
    total = 2 * d * cfg["vocab_size"]
    for w in windows(cfg):
        total += 2 * d * (2 * h * hd + 2 * kv * hd) + 2 * d * e
        total += k * held(cfg) / e * 3 * 2 * d * f
        total += 2 * 2 * h * hd * mean_keys(cfg["seq_len"], w)
    return total


def train_flops(cfg: dict, n_seqs: int, epochs: int) -> int:
    """Operations of full-batch SGD: per token and epoch the forward pass,
    and as many again twice for the input and the weight gradients; what
    a remat recomputes is not counted."""
    return int(3 * forward_flops_per_token(cfg) * n_seqs * cfg["seq_len"]
               * epochs)


def train_bytes(cfg: dict, n_seqs: int, epochs: int) -> int:
    """Least HBM traffic of one training call: the token ids and labels
    read once, the f32 parameters read and written once."""
    return int(n_seqs * cfg["seq_len"] * 2 * 4 + 2 * 4 * n_params(cfg))


# --- set-up -------------------------------------------------------------------

def _normal(key, shape, scale):
    return jax.random.normal(key, shape, F32) * scale


@functools.partial(jax.jit, static_argnames=("shape",))
def _init(key, shape):
    (periods, per, d, h, kv, hd, f, e, eh, v) = shape
    ks = jax.random.split(key, 9)
    lead = (periods, per)
    return {
        "embed": {"embedding": _normal(ks[0], (v, d), 0.02)},
        "lm_head": {"embedding": _normal(ks[1], (v, d), 0.02)},
        "final_norm": {"scale": jnp.zeros((d,), F32)},
        "blocks": {
            "ln1": {"scale": jnp.zeros(lead + (d,), F32)},
            "ln2": {"scale": jnp.zeros(lead + (d,), F32)},
            "attn": {
                "wq": _normal(ks[2], lead + (d, h, hd), d ** -0.5),
                "wk": _normal(ks[3], lead + (d, kv, hd), d ** -0.5),
                "wv": _normal(ks[4], lead + (d, kv, hd), d ** -0.5),
                "wo": _normal(ks[5], lead + (h, hd, d), (h * hd) ** -0.5)},
            "moe": {
                "router": _normal(ks[6], lead + (d, e), d ** -0.5),
                "wi_gate": _normal(ks[7], lead + (eh, d, f), d ** -0.5),
                "wi_up": _normal(ks[8], lead + (eh, d, f), d ** -0.5),
                "wo": _normal(jax.random.fold_in(ks[8], 1),
                              lead + (eh, f, d), f ** -0.5)}},
    }


def init_weights(key, cfg: dict) -> dict:
    """Normal weights at 1/sqrt(fan-in) (embedding and head 0.02), zero
    norm scales, made on the device in one jitted call from ``key``: the
    weights both the program and the reference start from.  One period
    holds every layer of the configuration."""
    d, h, kv, hd, f, e = _dims(cfg)
    if cfg["tie_word_embeddings"]:
        raise ValueError("the family holds an untied head")
    _built["cfg"] = cfg
    return _init(key, (1, cfg["num_hidden_layers"], d, h, kv, hd, f, e,
                       held(cfg), cfg["vocab_size"]))


def program_config(cfg: dict):
    """The program's ``ModelConfig`` of the configuration."""
    slide, full = rope(cfg, 1), rope(cfg, 0)
    if (slide["rope_type"], full["rope_type"]) != ("default", "yarn") or \
            slide["rope_theta"] != full["rope_theta"]:
        raise ValueError("the program's period has default RoPE on its "
                         "windowed layers and YaRN on its full ones, at one "
                         "theta")
    if cfg["rms_norm_eps"] != 1e-6 or cfg["hidden_act"] != "silu" or \
            not cfg["norm_topk_prob"] or cfg["attention_bias"]:
        raise ValueError("the program's blocks: RMSNorm eps 1e-6, SiLU, "
                         "renormalised top-k, no attention bias")
    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(full["rope_theta"]), layer_windows=windows(cfg),
        rope_yarn=(float(full["factor"]),
                   int(full["original_max_position_embeddings"]),
                   float(full["beta_fast"]), float(full["beta_slow"]),
                   float(full["attention_factor"])),
        n_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        experts_held=held(cfg), expert_offset=int(cfg["expert_offset"]),
        tie_embeddings=False, loss_chunk=int(cfg["loss_chunk"]),
        microbatches=int(cfg["microbatches"]))


def program_eval_fn(test_x: np.ndarray, test_y: np.ndarray, cfg=None):
    """The program's evaluation over a test set: ``lm_accuracy`` one
    sequence at a time, and the ``float`` it returns (``cfg``: the
    configuration built last by default)."""
    cfg = cfg or _built["cfg"]
    mcfg = program_config(cfg)
    tx, ty = jnp.asarray(test_x), jnp.asarray(test_y)
    return lambda w: lm_accuracy(w, tx, ty, mcfg)


def build_setup(cfg: dict, traffic, weights0):
    """The program's ``FLSetup`` over the traffic's shards and test set,
    with the program's own training and evaluation functions."""
    mcfg = program_config(cfg)
    model_bytes = int(sum(leaf.size * leaf.dtype.itemsize
                          for leaf in jax.tree.leaves(weights0)))
    return FLSetup(
        cfg=mcfg, weights0=weights0, shards=traffic.shards,
        profiles=traffic.profiles, test_x=traffic.test_x,
        test_y=traffic.test_y, model_bytes=model_bytes,
        train_fn=functools.partial(lm_train_wrapper, lr=float(cfg["lr"]),
                                   cfg=mcfg),
        eval_fn=program_eval_fn(traffic.test_x, traffic.test_y, cfg),
        per_batch_server=float(traffic.spec["per_batch_server"]))


# --- plain reference ----------------------------------------------------------

def inv_freq(head_dim: int, p: dict) -> np.ndarray:
    """RoPE's inverse frequencies, float64; for ``yarn`` the interpolated
    (``/ factor``) and extrapolated ones blended by the linear ramp
    between the dimensions that turn ``beta_fast`` and ``beta_slow`` times
    over the original context."""
    theta = float(p["rope_theta"])
    base = 1.0 / theta ** (np.arange(0, head_dim, 2) / head_dim)
    if p["rope_type"] == "default":
        return base
    orig = p["original_max_position_embeddings"]

    def dim(turns):
        return head_dim * math.log(orig / (2 * math.pi * turns)) / (
            2 * math.log(theta))
    low = max(math.floor(dim(p["beta_fast"])), 0)
    high = min(math.ceil(dim(p["beta_slow"])), head_dim - 1)
    ramp = np.clip((np.arange(head_dim // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    return base / p["factor"] * ramp + base * (1.0 - ramp)


def _rms(x, scale):
    x32 = x.astype(F32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + 1e-6)
    return (y * (1.0 + scale.astype(F32))).astype(x.dtype)


def _rotate(x, pos, freq, factor):
    """RoPE on (S, heads, hd) by halves, cos and sin times ``factor``."""
    ang = pos[:, None].astype(F32) * freq[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _attend(q, k, v, window):
    """Causal attention of (S, H, hd) queries over (S, KV, hd) keys and
    values, QUERY_BLOCK queries at a time against the keys their mask
    may admit, with the mask written out."""
    s, h, hd = q.shape
    kvh = k.shape[1]
    group = h // kvh
    qb = min(QUERY_BLOCK, s)

    @jax.checkpoint
    def block(qblk, kblk, vblk, qpos, kpos):
        sc = jnp.einsum("qcgd,kcd->cgqk", qblk.reshape(-1, kvh, group, hd),
                        kblk) / math.sqrt(hd)
        mask = kpos[None, :] <= qpos[:, None]
        if window:
            mask &= qpos[:, None] - kpos[None, :] < window
        sc = jnp.where(mask, sc.astype(F32), -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1).astype(vblk.dtype)
        return jnp.einsum("cgqk,kcd->qcgd", pr, vblk).reshape(-1, h, hd)

    out = []
    for lo_q in range(0, s, qb):
        hi = lo_q + qb
        lo = max(0, lo_q - window + 1) if window else 0
        out.append(block(q[lo_q:hi], k[lo:hi], v[lo:hi],
                         jnp.arange(lo_q, hi), jnp.arange(lo, hi)))
    return jnp.concatenate(out, 0)


def _experts(p, x, top_k, offset):
    """This chip's part of the expert layer for every token of (S, D):
    softmax over every expert, the top ``top_k`` renormalised, and each
    held expert's SwiGLU over every token times the token's gate for it
    (zero where the expert was not chosen)."""
    probs = jax.nn.softmax(jnp.dot(x, p["router"]).astype(F32), axis=-1)
    top, idx = jax.lax.top_k(probs, top_k)
    top = top / jnp.sum(top, -1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(idx, probs.shape[-1], dtype=F32)
                    * top[..., None], axis=1)
    eh = p["wi_gate"].shape[0]
    g = gates[:, offset:offset + eh].astype(x.dtype)           # (S, held)
    a = jnp.einsum("sd,edf->sef", x, p["wi_gate"])
    b = jnp.einsum("sd,edf->sef", x, p["wi_up"])
    hmid = jax.nn.silu(a) * b * g[:, :, None]
    return jnp.einsum("sef,efd->sd", hmid, p["wo"])


def _layer(p, x, window, rope_p, top_k, offset):
    s = x.shape[0]
    pos = jnp.arange(s)
    freq = jnp.asarray(inv_freq(p["attn"]["wq"].shape[-1], rope_p), F32)
    factor = float(rope_p.get("attention_factor", 1.0))
    h = _rms(x, p["ln1"]["scale"])
    a = p["attn"]
    q = _rotate(jnp.einsum("sd,dhk->shk", h, a["wq"]), pos, freq, factor)
    k = _rotate(jnp.einsum("sd,dhk->shk", h, a["wk"]), pos, freq, factor)
    v = jnp.einsum("sd,dhk->shk", h, a["wv"])
    x = x + jnp.einsum("shk,hkd->sd", _attend(q, k, v, window), a["wo"])
    return x + _experts(p["moe"], _rms(x, p["ln2"]["scale"]), top_k, offset)


def _logits(params, tokens, spec):
    """(S, V) logits of one sequence."""
    win, ropes, top_k, offset = spec
    x = params["embed"]["embedding"][tokens]
    blocks = params["blocks"]
    n_periods, per = blocks["ln1"]["scale"].shape[:2]
    for i in range(n_periods * per):
        p = jax.tree.map(lambda t: t[i // per, i % per], blocks)
        layer = jax.checkpoint(functools.partial(
            _layer, window=win[i], rope_p=dict(ropes[i]), top_k=top_k,
            offset=offset))
        x = layer(p, x)
    x = _rms(x, params["final_norm"]["scale"])
    return jnp.dot(x, params["lm_head"]["embedding"].T).astype(F32)


def _loss(params, tokens, labels, spec):
    lg = _logits(params, tokens, spec)
    gold = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - gold)


def _spec(cfg: dict) -> tuple:
    """What the reference's programs fix at trace time, hashable."""
    win = windows(cfg)
    ropes = tuple(tuple(sorted(rope(cfg, w).items())) for w in win)
    return win, ropes, cfg["num_experts_per_tok"], int(cfg["expert_offset"])


@functools.partial(jax.jit, static_argnames=("spec",))
def _seq_grad(params, tokens, labels, spec):
    return jax.grad(_loss)(params, tokens, labels, spec)


@jax.jit
def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


@functools.partial(jax.jit, static_argnames=("lr", "n"))
def _sgd_step(params, grad_sum, lr, n):
    return jax.tree.map(lambda p, g: (p - lr / n * g).astype(p.dtype),
                        params, grad_sum)


def train(cfg: dict, params, x, y, epochs: int, dtype=F32):
    """``epochs`` steps of full-batch SGD at ``cfg["lr"]``, the gradient of
    the mean next-token loss summed one sequence at a time.  At float32
    every product runs at ``HIGHEST`` precision; with bfloat16 (the
    control) the weights, every product and every update are bfloat16."""
    spec = _spec(cfg)
    precision = "highest" if dtype == F32 else "default"
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    with jax.default_matmul_precision(precision):
        for _ in range(int(epochs)):
            g = None
            for i in range(len(x)):
                gi = _seq_grad(p, jnp.asarray(x[i]), jnp.asarray(y[i]), spec)
                g = gi if g is None else _add(g, gi)
                del gi
            p = _sgd_step(p, g, lr=float(cfg["lr"]), n=len(x))
            del g
    return jax.tree.map(lambda a: a.astype(F32), p)


@functools.partial(jax.jit, static_argnames=("spec",))
def _seq_hits(params, tokens, labels, spec):
    return jnp.sum(jnp.argmax(_logits(params, tokens, spec), -1) == labels)


def control_train_fn(cfg: dict):
    """The reference's training with bfloat16 weights, with the program's
    ``train_fn`` signature: the control put in the program's place."""
    def fn(params, x, y, epochs):
        return train(cfg, params, np.asarray(x), np.asarray(y), epochs,
                     dtype=jnp.bfloat16)
    return fn


def ref_train(cfg: dict, params, x: np.ndarray, y: np.ndarray, epochs: int):
    return train(cfg, params, x, y, epochs)


def ref_accuracy(params, x: np.ndarray, y: np.ndarray, cfg=None) -> float:
    """Share of the test tokens whose next id the reference's largest
    logit names, one sequence at a time (``cfg``: the configuration built
    last by default)."""
    spec = _spec(cfg or _built["cfg"])
    with jax.default_matmul_precision("highest"):
        hits = sum(int(_seq_hits(params, jnp.asarray(x[i]),
                                 jnp.asarray(y[i]), spec))
                   for i in range(len(x)))
    return hits / y.size
