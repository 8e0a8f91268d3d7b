"""Shared layer primitives: RMSNorm, rotary embeddings, GLU MLPs, softcap.

Pure-functional: every layer is ``init(rng, ...) -> params`` plus an
``apply(params, x, ...)`` function. Params are plain dict pytrees so they
stack cleanly under ``jax.vmap`` / ``lax.scan`` and shard with logical-axis
annotations (see ``repro.parallel.sharding``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

COMPUTE_DTYPE = jnp.bfloat16
PARAM_DTYPE = jnp.float32   # master params; cast to bf16 for compute


def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    """Gemma-2 logit soft-capping: cap * tanh(x / cap)."""
    return (cap * jnp.tanh(x / cap)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int):
    return {"scale": jnp.zeros((d,), PARAM_DTYPE)}   # (1+scale) parameterisation


def rmsnorm(params, x, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * (1.0 + params["scale"].astype(jnp.float32))
    return y.astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_freqs(head_dim: int, theta: float, factor: float, original_max: int,
               beta_fast: float, beta_slow: float) -> jnp.ndarray:
    """YaRN's inverse frequencies (Peng et al. 2023, as Hugging Face's
    ``_compute_yarn_parameters``): the interpolated (``/ factor``) and the
    extrapolated frequencies blended by a linear ramp over the dimensions
    between the ``beta_fast`` and ``beta_slow`` rotation counts of the
    original context."""
    def dim_of(rotations):
        return (head_dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), head_dim - 1)
    extra = rope_freqs(head_dim, theta)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp                      # share of the extrapolated frequency
    return extra / factor * (1.0 - keep) + extra * keep


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               yarn=None, scale: float = 1.0) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: broadcastable to (..., S).  ``yarn``
    (``ModelConfig.rope_yarn``) scales the frequencies and multiplies cos
    and sin by its attention factor; ``scale`` multiplies them too, so a
    scaled x is rounded to its dtype once, after the f32 product."""
    hd = x.shape[-1]
    if yarn is None:
        freqs = rope_freqs(hd, theta)                    # (hd/2,)
    else:
        freqs = yarn_freqs(hd, theta, *yarn[:4])
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs  # (...,S,1,hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    amp = (yarn[4] if yarn is not None else 1.0) * scale
    if amp != 1.0:
        cos, sin = cos * amp, sin * amp
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense / GLU MLP
# ---------------------------------------------------------------------------

def _dense_init(rng, shape, in_axis: int = 0):
    fan_in = shape[in_axis]
    return (jax.random.normal(rng, shape, PARAM_DTYPE) / jnp.sqrt(fan_in))


def glu_mlp_init(rng, d_model: int, d_ff: int):
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "wi_gate": _dense_init(k1, (d_model, d_ff)),
        "wi_up": _dense_init(k2, (d_model, d_ff)),
        "wo": _dense_init(k3, (d_ff, d_model)),
    }


def glu_mlp(params, x, act: str = "silu"):
    dt = x.dtype
    gate = x @ params["wi_gate"].astype(dt)
    up = x @ params["wi_up"].astype(dt)
    if act == "silu":
        h = jax.nn.silu(gate) * up
    elif act == "gelu":
        h = jax.nn.gelu(gate, approximate=True) * up
    else:
        raise ValueError(act)
    # bf16 partial sums across the model-sharded d_ff contraction
    return jnp.einsum("bsf,fd->bsd", h, params["wo"].astype(dt),
                      preferred_element_type=dt)


# ---------------------------------------------------------------------------
# Embedding + chunked vocab loss
# ---------------------------------------------------------------------------

def embed_init(rng, vocab: int, d_model: int):
    return {"embedding": jax.random.normal(rng, (vocab, d_model), PARAM_DTYPE) * 0.02}


def embed(params, tokens, scale: bool = False):
    e = params["embedding"].astype(COMPUTE_DTYPE)[tokens]
    if scale:
        e = e * jnp.asarray(jnp.sqrt(e.shape[-1]), e.dtype)
    return e


def chunked_ce_loss(head_params, h, labels, *, chunk: int, final_softcap: float = 0.0,
                    mask=None):
    """Cross-entropy with the LM head (``{"embedding": (V, D)}``: the tied
    embedding or an untied head) applied in sequence chunks so the full
    (B,S,V) logits tensor never materialises. h: (B,S,D), labels: (B,S)."""
    B, S, D = h.shape
    table = head_params["embedding"].astype(COMPUTE_DTYPE)    # (V, D)
    chunk = min(chunk, S)
    n_chunks = S // chunk
    assert S % chunk == 0, (S, chunk)
    h = h.reshape(B, n_chunks, chunk, D).swapaxes(0, 1)       # (n,B,c,D)
    labels = labels.reshape(B, n_chunks, chunk).swapaxes(0, 1)
    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    mask = mask.reshape(B, n_chunks, chunk).swapaxes(0, 1)

    def body(carry, xs):
        hc, lc, mc = xs
        logits = hc @ table.T                                  # (B,c,V)
        if final_softcap:
            logits = softcap(logits, final_softcap)
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * mc
        return carry + jnp.sum(nll), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (h, labels, mask))
    return total / jnp.maximum(jnp.sum(mask), 1.0)
