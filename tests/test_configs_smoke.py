"""Per-architecture smoke tests: reduced same-family config, one forward /
train step + one prefill->decode step on CPU; asserts shapes and no NaNs."""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro import optim
from repro.configs import get_config, list_archs
from repro.models import (init_params, prefill_step, serve_step, train_step)

ARCHS = list_archs()


def _batch(cfg, rng, B=2, S=32):
    if cfg.embeds_input:
        return {"embeds": jax.random.normal(rng, (B, S, cfg.d_model),
                                            jnp.bfloat16),
                "labels": jax.random.randint(rng, (B, S), 0, cfg.vocab_size)}
    return {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
            "labels": jax.random.randint(rng, (B, S), 0, cfg.vocab_size)}


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_train_step(arch):
    cfg = get_config(arch, reduced=True)
    rng = jax.random.PRNGKey(0)
    params = init_params(rng, cfg)
    batch = _batch(cfg, rng)
    opt = optim.adamw(1e-3)
    ost = opt.init(params)
    step = jax.jit(functools.partial(train_step, cfg=cfg, optimizer=opt))
    p2, o2, m = step(params, ost, batch)
    loss = float(m["loss"])
    assert jnp.isfinite(m["loss"]), f"{arch}: non-finite loss"
    assert 0.0 < loss < 20.0
    # params actually changed
    changed = jax.tree.map(lambda a, b: bool(jnp.any(a != b)), params, p2)
    assert any(jax.tree.leaves(changed)), f"{arch}: no param moved"


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_prefill_decode(arch):
    cfg = get_config(arch, reduced=True)
    rng = jax.random.PRNGKey(1)
    params = init_params(rng, cfg)
    B, S = 2, 32
    batch = _batch(cfg, rng, B, S)
    logits, state = jax.jit(functools.partial(prefill_step, cfg=cfg))(
        params, batch)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert not bool(jnp.any(jnp.isnan(logits.astype(jnp.float32))))
    sv = jax.jit(functools.partial(serve_step, cfg=cfg))
    if cfg.embeds_input:
        lg, st2 = sv(params, state, None, jnp.int32(S),
                     embeds=jnp.zeros((B, 1, cfg.d_model), jnp.bfloat16))
    else:
        lg, st2 = sv(params, state, jnp.zeros((B, 1), jnp.int32), jnp.int32(S))
    assert lg.shape == (B, 1, cfg.vocab_size)
    assert not bool(jnp.any(jnp.isnan(lg.astype(jnp.float32))))


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_shapes(arch):
    """The FULL configs match the assigned spec (exercised end-to-end only
    via the dry-run; here we check the published numbers)."""
    cfg = get_config(arch)
    spec = {
        "gemma2-2b": (26, 2304, 8, 4, 9216, 256000),
        "yi-9b": (48, 4096, 32, 4, 11008, 64000),
        "deepseek-67b": (95, 8192, 64, 8, 22016, 102400),
        "starcoder2-15b": (40, 6144, 48, 4, 24576, 49152),
        "mixtral-8x22b": (56, 6144, 48, 8, 16384, 32768),
        "phi3.5-moe-42b-a6.6b": (32, 4096, 32, 8, 6400, 32064),
        "rwkv6-3b": (32, 2560, 40, 40, 8960, 65536),
        "zamba2-7b": (81, 3584, 32, 32, 14336, 32000),
        "internvl2-26b": (48, 6144, 48, 8, 16384, 92553),
        "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
        "mellum2-12b-a2.5b": (28, 2304, 32, 4, 896, 98304),
    }[arch]
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.d_ff, cfg.vocab_size) == spec


def test_moe_is_moe():
    assert get_config("mixtral-8x22b").n_experts == 8
    assert get_config("mixtral-8x22b").top_k == 2
    assert get_config("phi3.5-moe-42b-a6.6b").n_experts == 16


def test_zamba2_layer_arithmetic():
    cfg = get_config("zamba2-7b")
    g = cfg.n_shared_attn_applications()
    assert g == 13
    assert g * (cfg.shared_attn_every + 1) + 3 == cfg.n_layers == 81
