"""Mellum2's period of windowed and full YaRN layers, its dropless expert
share and its untied head, at ``REDUCED`` on the CPU, against a plain f32
reference written here from the published description: every product at
``HIGHEST`` precision, attention as one masked softmax, experts as dense
products over every token masked by their gates."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.mellum2_12b_a2p5b import CONFIG, SHARE
from repro.core import experiment
from repro.models import attention, layers, moe, transformer
from repro.models.layers import apply_rope, rope_freqs, yarn_freqs
from repro.runtime import trace

RED = get_config("mellum2-12b-a2.5b", reduced=True)
F32 = jnp.float32


# --- the plain reference -------------------------------------------------------

def ref_inv_freq(hd, theta, yarn):
    """RoPE's inverse frequencies in float64; YaRN as Hugging Face's
    ``_compute_yarn_parameters`` writes it."""
    base = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    if yarn is None:
        return base, 1.0
    factor, orig, fast, slow, mscale = yarn
    corr = lambda rot: hd * math.log(orig / (rot * 2 * math.pi)) / (
        2 * math.log(theta))
    low, high = max(math.floor(corr(fast)), 0), min(math.ceil(corr(slow)),
                                                     hd - 1)
    ramp = np.clip((np.arange(hd // 2) - low) / max(high - low, 1e-3), 0, 1)
    extrapolation = 1.0 - ramp
    inv = base / factor * (1 - extrapolation) + base * extrapolation
    return inv, mscale


def ref_rope(x, yarn, theta):
    s, _, hd = x.shape
    inv, mscale = ref_inv_freq(hd, theta, yarn)
    ang = np.arange(s)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang) * mscale, F32)[:, None, :]
    sin = jnp.asarray(np.sin(ang) * mscale, F32)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def ref_rms(x, scale):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * (1 + scale)


def ref_experts(p, x, cfg):
    """(S, D) -> the held experts' part: softmax over every expert, top-k
    renormalised, each held expert over every token times its gate."""
    probs = jax.nn.softmax(x @ p["router"], -1)
    top, idx = jax.lax.top_k(probs, cfg.top_k)
    top = top / top.sum(-1, keepdims=True)
    gates = (jax.nn.one_hot(idx, cfg.n_experts) * top[..., None]).sum(1)
    held = cfg.n_experts_here
    out = 0.0
    for e in range(held):
        g = gates[:, cfg.expert_offset + e][:, None]
        h = jax.nn.silu(x @ p["wi_gate"][e]) * (x @ p["wi_up"][e])
        out = out + g * (h @ p["wo"][e])
    return out


def ref_layer(p, x, cfg, window):
    s = x.shape[0]
    h = ref_rms(x, p["ln1"]["scale"])
    a = p["attn"]
    yarn = cfg.rope_yarn if window == 0 else None
    q = ref_rope(jnp.einsum("sd,dhk->shk", h, a["wq"]), yarn, cfg.rope_theta)
    k = ref_rope(jnp.einsum("sd,dhk->shk", h, a["wk"]), yarn, cfg.rope_theta)
    v = jnp.einsum("sd,dhk->shk", h, a["wv"])
    rep = cfg.n_heads // cfg.n_kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(cfg.hd)
    qi, ki = np.arange(s)[:, None], np.arange(s)[None, :]
    mask = (ki <= qi) & ((qi - ki < window) if window else True)
    sc = jnp.where(jnp.asarray(mask)[None], sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
    x = x + jnp.einsum("shk,hkd->sd", o, a["wo"])
    return x + ref_experts(p["moe"], ref_rms(x, p["ln2"]["scale"]), cfg)


def ref_logits(params, tokens, cfg):
    """(S,) ids -> (S, V) logits."""
    x = params["embed"]["embedding"][tokens]
    P = len(cfg.period)
    for i in range(cfg.n_layers):
        p = jax.tree.map(lambda t: t[i // P, i % P], params["blocks"])
        x = ref_layer(p, x, cfg, cfg.period[i % P])
    x = ref_rms(x, params["final_norm"]["scale"])
    return x @ params["lm_head"]["embedding"].T


def ref_loss(params, tokens, labels, cfg):
    lg = jax.vmap(lambda t: ref_logits(params, t, cfg))(tokens)
    gold = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(lg, -1) - gold)


def f32_params(cfg, seed=0):
    """Seeded weights at f32, norm scales random too (they start at 0)."""
    p = jax.tree.map(lambda a: a.astype(F32),
                     transformer.init_params(jax.random.PRNGKey(seed), cfg))
    k = jax.random.PRNGKey(seed + 1)
    for name in ("ln1", "ln2"):
        s = p["blocks"][name]["scale"]
        p["blocks"][name]["scale"] = 0.1 * jax.random.normal(k, s.shape)
        k = jax.random.fold_in(k, 1)
    return p


def tokens(cfg, b, s, seed=3):
    t = jax.random.randint(jax.random.PRNGKey(seed), (b, s + 1), 0,
                           cfg.vocab_size)
    return t[:, :-1], t[:, 1:]


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# --- RoPE ------------------------------------------------------------------------

def test_yarn_frequencies_follow_the_formula():
    for hd, yarn in ((128, CONFIG.rope_yarn), (RED.hd, RED.rope_yarn)):
        got = np.asarray(yarn_freqs(hd, 500_000.0, *yarn[:4]), np.float64)
        want, _ = ref_inv_freq(hd, 500_000.0, yarn)
        # float32 powers of theta against float64: a few ulps
        np.testing.assert_allclose(got, want, rtol=2e-6)
        # the ramp blends: the fastest dimension extrapolates, the slowest
        # interpolates by the factor
        base = ref_inv_freq(hd, 500_000.0, None)[0]
        assert got[0] == pytest.approx(base[0], rel=1e-6)
        assert got[-1] == pytest.approx(base[-1] / yarn[0], rel=1e-6)


def test_yarn_rope_scales_cos_and_sin():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 2, 128), F32)
    pos = jnp.arange(40)[None]
    got = apply_rope(x, pos, 500_000.0, CONFIG.rope_yarn)[0]
    want = ref_rope(x[0], CONFIG.rope_yarn, 500_000.0)
    # f32 angles of positions < 40: rounding of the sine and cosine only
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the attention factor multiplies every pair's norm
    n = lambda v: np.linalg.norm(np.asarray(v), axis=-1)
    np.testing.assert_allclose(n(got), n(x[0]) * CONFIG.rope_yarn[4],
                               rtol=1e-5)


def test_default_rope_is_the_old_expression_bit_for_bit():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 4, 32), F32)
    pos = jnp.broadcast_to(jnp.arange(16), (2, 16))
    ang = pos[..., :, None, None].astype(F32) * rope_freqs(32, 10_000.0)
    x1, x2 = jnp.split(x, 2, axis=-1)
    old = jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                           x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)
    assert np.array_equal(apply_rope(x, pos, 10_000.0), old)


# --- the model ---------------------------------------------------------------------

def test_period_forward_logits_match_the_reference():
    params = f32_params(RED)
    x, _ = tokens(RED, 2, 32)
    h, _, _ = transformer.forward(params, RED, tokens=x)
    got = transformer.logits_from_hidden(params, RED, h.astype(F32))
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda t: ref_logits(params, t, RED))(x)
    # the program computes in bf16 (8 significant bits) through 8 layers;
    # at bf16 every layer's rounding (~0.4%) adds up to a few percent
    err = rel(got, want)
    assert err < 0.05, err
    # and the untied head is what it reads: the tied table gives other logits
    tied = h.astype(F32) @ params["embed"]["embedding"].T
    assert rel(tied, want) > 0.5


def assert_step_matches_the_reference(params, new, x, y, lr, whole=0.05):
    with jax.default_matmul_precision("highest"):
        g = jax.grad(ref_loss)(params, x, y, RED)
    want = jax.tree.map(lambda p, gg: -lr * gg, params, g)
    got = jax.tree.map(jnp.subtract, new, params)
    # the f32 weights' change from bf16 gradients: the norm of each leaf's
    # change within 10% of the reference's, the whole change within
    # ``whole`` (5%)
    flat = lambda t: np.concatenate([np.ravel(v) for v in jax.tree.leaves(t)])
    assert rel(flat(got), flat(want)) < whole
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        nb = np.linalg.norm(b)
        if nb > 1e-3 * np.linalg.norm(flat(want)):
            assert abs(np.linalg.norm(a) - nb) / nb < 0.1
    # the master weights stay f32
    assert all(v.dtype == F32 for v in jax.tree.leaves(new))


def test_one_local_sgd_step_matches_the_reference():
    params = f32_params(RED)
    x, y = tokens(RED, 4, 32)
    lr = 0.5
    new = transformer.lm_sgd_train(params, x, y, epochs=1, lr=lr, cfg=RED)
    assert_step_matches_the_reference(params, new, x, y, lr)


@pytest.fixture
def kernel_forced(monkeypatch):
    """Every attention layer in the splash kernel (interpret mode), at
    blocks of 128 so that the tiny config's sequences hold whole blocks;
    no program traced under it outlives the test.  Yields the windows of
    the layers traced through the kernel."""
    windows, kernel = [], attention.splash_attention

    def spy(*a, **k):
        windows.append(k["window"])
        return kernel(*a, **k)
    monkeypatch.setattr(attention, "attn_kernel_takes", lambda *a, **k: True)
    monkeypatch.setattr(attention, "SPLASH_BLOCK", 128)
    monkeypatch.setattr(attention, "splash_attention", spy)
    transformer.lm_sgd_train.clear_cache()
    yield windows
    transformer.lm_sgd_train.clear_cache()


def test_one_local_sgd_step_through_the_kernel_matches_the_reference(
        kernel_forced, monkeypatch):
    """At the kernel's smallest block, 128 tokens, bf16 rounding flips
    routing choices and puts either attention path 6-13% from the
    reference; computed in f32, the kernel is the step's one departure
    from the reference's arithmetic, and it lands within 1e-4."""
    monkeypatch.setattr(layers, "COMPUTE_DTYPE", F32)
    monkeypatch.setattr(transformer, "COMPUTE_DTYPE", F32)
    params = f32_params(RED)
    x, y = tokens(RED, 4, 128)
    lr = 0.5
    new = transformer.lm_sgd_train(params, x, y, epochs=1, lr=lr, cfg=RED)
    assert_step_matches_the_reference(params, new, x, y, lr, whole=1e-4)
    # each layer of the period went through the kernel, windowed and full
    assert set(kernel_forced) == set(RED.period) == {8, 0}


def test_decode_matches_forward_through_the_period():
    """Prefill and one-token decode through the windowed and YaRN caches
    give the full forward's logits.  Every expert is chosen, so that bf16
    rounding cannot flip a routing choice between the two paths."""
    cfg = RED.replace(top_k=RED.n_experts)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    x, _ = tokens(cfg, 2, 16)
    h, _, _ = transformer.forward(params, cfg, tokens=x)
    want = transformer.logits_from_hidden(params, cfg, h)[:, 7:]
    lg, st = transformer.prefill_step(params, {"tokens": x[:, :8]}, cfg=cfg,
                                      max_len=16)
    outs = [lg[:, 0]]
    for t in range(8, 16):
        lg, st = transformer.serve_step(params, st, x[:, t:t + 1],
                                        jnp.int32(t), cfg=cfg)
        outs.append(lg[:, 0])
    got = jnp.stack(outs, 1).astype(F32)
    want = want.astype(F32)
    # bf16 compute on two paths (blockwise vs cached attention)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert err < 0.03, err


# --- the expert share --------------------------------------------------------------

def _moe_params(cfg, seed=0):
    p = moe.moe_init(jax.random.PRNGKey(seed), cfg.d_model, cfg.d_ff,
                     cfg.n_experts)
    return jax.tree.map(lambda a: a.astype(F32), p)


def _share(p, offset, held):
    return {"router": p["router"],
            **{k: p[k][offset:offset + held]
               for k in ("wi_gate", "wi_up", "wo")}}


def test_expert_shares_add_up_to_the_whole_layer():
    """Four chips of two experts each: their parts of the layer add up to
    what the uncut layer gives, and each part is the reference's."""
    p = _moe_params(RED)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, RED.d_model), F32)
    with jax.default_matmul_precision("highest"):
        whole, aux = moe.moe_share_apply(p, x, top_k=RED.top_k)
        parts = []
        for off in range(0, RED.n_experts, 2):
            y, _ = moe.moe_share_apply(_share(p, off, 2), x, top_k=RED.top_k,
                                       expert_offset=off)
            cfg = RED.replace(experts_held=2, expert_offset=off)
            ref = ref_experts(_share(p, off, 2), x.reshape(-1, RED.d_model),
                              cfg)
            # f32 throughout: the sums differ in order only
            np.testing.assert_allclose(y.reshape(ref.shape), ref, rtol=1e-4,
                                       atol=1e-5)
            parts.append(y)
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-4, atol=1e-5)
    assert float(aux) == 0.0


def test_dropless_when_every_token_picks_one_held_expert():
    """Every token routes to expert 1 (held by the share at offset 0): all
    N rows land in one group and none is dropped, where capacity dispatch
    would keep 1.25 N top_k / E of them."""
    p = _moe_params(RED)
    p["router"] = p["router"].at[:, 1].add(50.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6),
                                  (1, 64, RED.d_model), F32)) + 0.5
    share = _share(p, 0, 2)
    cfg = RED.replace(experts_held=2, expert_offset=0)
    with jax.default_matmul_precision("highest"):
        y, _ = moe.moe_share_apply(share, x, top_k=RED.top_k)
        ref = ref_experts(share, x[0], cfg)
        probs = jax.nn.softmax(x[0] @ p["router"], -1)
    assert bool(jnp.all(jnp.argmax(probs, -1) == 1))
    np.testing.assert_allclose(y[0], ref, rtol=1e-4, atol=1e-5)
    assert bool(jnp.all(jnp.linalg.norm(y[0], axis=-1) > 0))


# --- counts and the federation's wrappers ------------------------------------------

def test_the_share_holds_340_349_184_parameters():
    assert SHARE.n_params() == 340_349_184
    assert (SHARE.n_layers, SHARE.experts_held, SHARE.n_experts,
            SHARE.vocab_size) == (4, 8, 64, 12_288)
    # the router's width and the widths stay the published ones
    assert (SHARE.d_model, SHARE.d_ff, SHARE.top_k, SHARE.hd) == (
        CONFIG.d_model, CONFIG.d_ff, CONFIG.top_k, CONFIG.hd)
    # a token meets 8 of 64 experts, 1 of them here on average
    expert = 3 * SHARE.d_model * SHARE.d_ff
    assert SHARE.n_params() - SHARE.n_active_params() == 4 * 7 * expert


ATTN_ARCHS = ["gemma2-2b", "yi-9b", "mixtral-8x22b", "phi3.5-moe-42b-a6.6b",
              "mellum2-12b-a2.5b"]


@pytest.mark.parametrize("arch", ATTN_ARCHS)
def test_n_params_counts_what_init_makes(arch):
    cfg = get_config(arch, reduced=True)
    shapes = jax.eval_shape(lambda k: transformer.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    assert cfg.n_params() == sum(int(np.prod(s.shape))
                                 for s in jax.tree.leaves(shapes))


def test_lm_wrappers_count_tokens_and_score_next_ids():
    params = f32_params(RED)
    x, y = tokens(RED, 4, 16)
    x, y = np.asarray(x), np.asarray(y)
    before = trace.counters().get("train_tokens", 0)
    out = experiment.lm_train_wrapper(params, x, y, 3, lr=0.1, cfg=RED)
    assert trace.counters()["train_tokens"] - before == 4 * 16 * 3
    assert jax.tree.structure(out) == jax.tree.structure(params)
    acc = experiment.lm_accuracy(params, x, y, RED, block=3)
    h, _, _ = transformer.forward(params, RED, tokens=jnp.asarray(x))
    pred = jnp.argmax(transformer.logits_from_hidden(params, RED, h), -1)
    assert acc == pytest.approx(float(jnp.mean(pred == y)))


@pytest.mark.parametrize("forced", [True, False])
def test_attn_kernel_tokens_count_the_kernel_layers(request, forced):
    """tokens x epochs x attention layers where the rule takes the kernel;
    none on the CPU, where it keeps the chunked path."""
    if forced:
        request.getfixturevalue("kernel_forced")
    params = f32_params(RED)
    x, y = (np.asarray(t) for t in tokens(RED, 2, 128))
    before = trace.counters().get("attn_kernel_tokens", 0)
    experiment.lm_train_wrapper(params, x, y, 2, lr=0.1, cfg=RED)
    got = trace.counters().get("attn_kernel_tokens", 0) - before
    assert got == (2 * 128 * 2 * RED.n_layers if forced else 0)
    assert RED.n_layers == 8


def test_rows_past_the_held_groups_never_reach_the_result(monkeypatch):
    """The grouped products leave the rows past the held groups
    unspecified (garbage on the TPU, zeros on the CPU): here they are NaN,
    forward and backward, and the layer's output and gradients stay those
    of the clean products."""
    real = jax.lax.ragged_dot

    @jax.custom_vjp
    def dirty(lhs, rhs, gs):
        out = real(lhs, rhs, gs)
        tail = jnp.arange(out.shape[0]) >= jnp.sum(gs)
        return jnp.where(tail[:, None], jnp.nan, out)

    def dirty_fwd(lhs, rhs, gs):
        return dirty(lhs, rhs, gs), (lhs, rhs, gs)

    def dirty_bwd(res, g):
        lhs, rhs, gs = res
        _, vjp = jax.vjp(lambda a, b: real(a, b, gs), lhs, rhs)
        dl, dr = vjp(g)
        tail = jnp.arange(dl.shape[0]) >= jnp.sum(gs)
        return jnp.where(tail[:, None], jnp.nan, dl), dr, None

    dirty.defvjp(dirty_fwd, dirty_bwd)
    p = _share(_moe_params(RED), 2, 2)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 16, RED.d_model), F32)

    def loss(p, x):
        y, _ = moe.moe_share_apply(p, x, top_k=RED.top_k, expert_offset=2)
        return jnp.sum(y * y)
    clean = jax.grad(loss, (0, 1))(p, x)
    monkeypatch.setattr(jax.lax, "ragged_dot", dirty)
    got = jax.grad(loss, (0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(clean)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
