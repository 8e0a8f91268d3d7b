"""Transport layer: codec round-trips on packed flat buffers in BOTH
directions, exact wire-byte accounting (bitmap + scales + payload
itemsize), per-link error feedback (uplink and downlink residuals), the
last-acked downlink base protocol (ack only at fetch completion), the
fused topk+int8 Pallas kernel vs its XLA oracle, bandwidth-learning
estimation, selection pricing from expected codec'd bytes, warehouse
ticket hygiene, and the end-to-end byte counters in HistoryPoint."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TABLE_4_1, make_setup, run_fl, time_to_accuracy
from repro.core import flatbuf, transport
from repro.core.estimator import TimeEstimator, WorkerProfile
from repro.core.warehouse import DataWarehouse
from repro.kernels import ref, topk_quant

N_PARAMS = 1000      # {"a": (30,30), "b": (100,)} below


def _model(seed, scale=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return {"a": jax.random.normal(ks[0], (30, 30)) * scale,
            "b": jax.random.normal(ks[1], (100,)) * scale}


def _vec_err(a, b):
    return float(jnp.max(jnp.abs(a - b)))


# ---------------- the fused kernel vs its XLA oracle ----------------

@pytest.mark.parametrize("N", [100, 512, 777, 2048])
def test_topk_quant_encode_kernel_matches_reference(N):
    x = jax.random.normal(jax.random.PRNGKey(0), (N,))
    thresh = float(jnp.sort(jnp.abs(x))[int(N * 0.9)])
    scale = float(jnp.max(jnp.abs(x))) / 127.0
    q_p, r_p = topk_quant.topk_quant_encode(x, thresh, scale,
                                            use_pallas=True, interpret=True)
    q_r, r_r = ref.reference_topk_quant_encode(x, thresh, scale)
    assert jnp.array_equal(q_p, q_r)
    assert _vec_err(r_p, r_r) < 1e-6


@pytest.mark.parametrize("N", [512, 333])
def test_dequant_add_kernel_matches_reference(N):
    q = jax.random.randint(jax.random.PRNGKey(1), (N,), -127, 128,
                           dtype=jnp.int8)
    base = jax.random.normal(jax.random.PRNGKey(2), (N,))
    out_p = topk_quant.dequant_add(q, 0.013, base,
                                   use_pallas=True, interpret=True)
    out_r = ref.reference_dequant_add(q, 0.013, base)
    assert _vec_err(out_p, out_r) < 1e-6


def test_encode_decode_kernel_roundtrip_bounded_error():
    """Quantisation error of the kept coordinates is bounded by scale/2."""
    x = jax.random.normal(jax.random.PRNGKey(3), (1024,))
    scale = float(jnp.max(jnp.abs(x))) / 127.0
    q, resid = topk_quant.topk_quant_encode(x, 0.0, scale)
    recon = topk_quant.dequant_add(q, scale, jnp.zeros_like(x))
    assert float(jnp.max(jnp.abs(recon - x))) <= scale * 0.51
    assert _vec_err(resid, x - recon) < 1e-6


# ---------------- codec round trips + exact wire bytes ----------------

def _roundtrip(codec, frac=0.1, seed=0):
    base = _model(seed)
    new = _model(seed + 1, scale=0.5)
    t = transport.Transport(base, codec=codec, down_codec="raw", frac=frac)
    link = t.link("w0")
    down = link.encode_down(base)
    assert down.wire_bytes == t.raw_bytes == 4 * N_PARAMS
    assert link.decode_down(down) is base        # downlink is raw/lossless
    up = link.encode_up(new)
    vec = link.decode_up_vec(up)
    tree = t.bundle.unpack(vec)
    return t, link, up, vec, tree, base, new


def test_raw_codec_exact_roundtrip():
    t, link, up, vec, tree, base, new = _roundtrip("raw")
    assert up.wire_bytes == 4 * N_PARAMS
    assert all(jnp.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(tree), jax.tree.leaves(new)))


def test_delta_codec_exact_roundtrip_and_bytes():
    t, link, up, vec, tree, base, new = _roundtrip("delta")
    assert up.wire_bytes == 4 * N_PARAMS
    assert all(jnp.allclose(a, b, atol=1e-6) for a, b in
               zip(jax.tree.leaves(tree), jax.tree.leaves(new)))


def test_int8_codec_bytes_and_error_bound():
    t, link, up, vec, tree, base, new = _roundtrip("int8")
    assert up.wire_bytes == N_PARAMS + 4         # payload + one f32 scale
    q, scale = up.data
    err = max(float(jnp.max(jnp.abs(a - b))) for a, b in
              zip(jax.tree.leaves(tree), jax.tree.leaves(new)))
    assert err <= float(scale) * 0.51


def test_topk_ef_codec_bytes_spec():
    t, link, up, vec, tree, base, new = _roundtrip("topk_ef", frac=0.1)
    k = transport.topk_k(N_PARAMS, 0.1)
    kept = int(jnp.sum(up.data != 0))
    assert kept <= k                       # generic data: no threshold ties
    assert up.wire_bytes == transport.bitmap_bytes(N_PARAMS) + 4 * kept
    # what was dropped is exactly the link's EF residual
    full = t.bundle.pack(new) - link.tx_base
    assert _vec_err(link.residual, full - up.data) < 1e-6


def test_topk_ef_int8_codec_bytes_spec():
    t, link, up, vec, tree, base, new = _roundtrip("topk_ef+int8", frac=0.1)
    q, scale = up.data
    kept = int(jnp.sum(q != 0))
    assert up.wire_bytes >= transport.bitmap_bytes(N_PARAMS) + 4 + kept
    assert up.wire_bytes <= (transport.bitmap_bytes(N_PARAMS) + 4
                             + transport.topk_k(N_PARAMS, 0.1))


def test_expected_up_bytes_match_actual_for_deterministic_codecs():
    for codec in ("raw", "delta", "int8"):
        t, link, up, *_ = _roundtrip(codec)
        assert up.wire_bytes == t.expected_up_bytes()
        assert link.upfront_up_bytes() == up.wire_bytes
    for codec in ("topk_ef", "topk_ef+int8"):
        t, link, up, *_ = _roundtrip(codec)
        assert link.upfront_up_bytes() is None
        assert up.wire_bytes <= t.expected_up_bytes()


def test_expected_oneway_bytes_raw_equals_model_bytes():
    t = transport.Transport(_model(0), codec="raw")
    assert t.expected_oneway_bytes() == t.raw_bytes
    tc = transport.Transport(_model(0), codec="topk_ef+int8", frac=0.1)
    assert tc.expected_oneway_bytes() < t.expected_oneway_bytes()


def test_zero_delta_ships_almost_nothing():
    """An echoing worker (no local data) must not pay full price: an all-
    zero delta keeps nothing under the threshold tie-guard."""
    base = _model(0)
    t = transport.Transport(base, codec="topk_ef+int8", frac=0.1)
    link = t.link("w0")
    link.encode_down(base)
    up = link.encode_up(base)                    # new == base: zero delta
    assert up.wire_bytes == transport.bitmap_bytes(N_PARAMS) + 4
    assert _vec_err(link.decode_up_vec(up), link.tx_base) == 0.0


def test_unknown_codec_rejected():
    with pytest.raises(ValueError):
        transport.Transport(_model(0), codec="gzip")


@pytest.mark.parametrize("codec", ["raw", "int8"])
def test_nonpackable_only_raw(codec):
    """A weight tree with a non-array leaf has no path through the
    transport under any codec: rejected at construction, naming the leaf."""
    with pytest.raises(ValueError, match="'a'"):
        transport.Transport({"a": "not-an-array"}, codec=codec,
                            raw_bytes=123)


# ---------------- downlink codecs: last-acked base protocol ----------------

def _ack_roundtrip(down_codec, frac=0.1):
    """First dispatch (raw fallback) + ack, then one codec'd dispatch."""
    base = _model(0)
    t = transport.Transport(base, codec="raw", down_codec=down_codec,
                            frac=frac)
    link = t.link("w0")
    d0 = link.encode_down(base)
    assert d0.codec == "raw" and d0.wire_bytes == t.raw_bytes
    assert link.acked_base is None               # not acked until fetched
    link.complete_fetch(d0)
    assert _vec_err(link.acked_base, t.bundle.pack(base)) == 0.0
    new = _model(1, scale=0.5)
    d1 = link.encode_down(new)
    return t, link, d1, base, new


@pytest.mark.parametrize("codec", ["delta", "int8", "topk_ef",
                                   "topk_ef+int8"])
def test_downlink_first_dispatch_raw_then_codec(codec):
    t, link, d1, base, new = _ack_roundtrip(codec)
    assert d1.codec == codec
    # dense f32 delta costs exactly the f32 model; the rest compress
    assert d1.wire_bytes <= t.raw_bytes
    if codec != "delta":
        assert d1.wire_bytes < t.raw_bytes
    # worker-side decode against the acked base == the server's prediction
    # of the worker-visible model (tx_base), bit for bit
    assert _vec_err(link.decode_down_vec(d1), link.tx_base) == 0.0


def test_downlink_delta_codec_lossless():
    t, link, d1, base, new = _ack_roundtrip("delta")
    assert d1.wire_bytes == 4 * N_PARAMS
    tree = link.complete_fetch(d1)
    assert all(jnp.allclose(a, b, atol=1e-6) for a, b in
               zip(jax.tree.leaves(tree), jax.tree.leaves(new)))
    assert _vec_err(link.acked_base, t.bundle.pack(new)) < 1e-6


def test_downlink_int8_codec_bytes_and_error_bound():
    t, link, d1, base, new = _ack_roundtrip("int8")
    assert d1.wire_bytes == N_PARAMS + 4
    q, scale = d1.data
    tree = link.complete_fetch(d1)
    err = max(float(jnp.max(jnp.abs(a - b))) for a, b in
              zip(jax.tree.leaves(tree), jax.tree.leaves(new)))
    assert err <= float(scale) * 0.51


def test_downlink_topk_bytes_spec():
    t, link, d1, base, new = _ack_roundtrip("topk_ef")
    kept = int(jnp.sum(d1.data != 0))
    assert kept <= transport.topk_k(N_PARAMS, 0.1)
    assert d1.wire_bytes == transport.bitmap_bytes(N_PARAMS) + 4 * kept
    # what was dropped is exactly the link's downlink EF residual
    full = t.bundle.pack(new) - link.acked_base
    assert _vec_err(link.down_residual, full - d1.data) < 1e-6


def test_downlink_ack_advances_only_at_fetch_complete():
    t, link, d1, base, new = _ack_roundtrip("topk_ef+int8")
    acked_before = link.acked_base
    # encoding alone must not move the ack (the worker hasn't fetched)
    assert link.acked_base is acked_before
    link.complete_fetch(d1)
    assert link.acked_base is not acked_before
    assert _vec_err(link.acked_base, link.tx_base) == 0.0


def test_downlink_restore_reverts_ef_residual_not_credits():
    """A cancelled fetch rolls the downlink EF residual back to its
    pre-encode value: the next dispatch's delta (model - acked_base)
    already re-carries the cancelled payload's mass, so an uplink-style
    reconstruction credit would double-count it."""
    t, link, d1, base, new = _ack_roundtrip("topk_ef+int8")
    acked = link.acked_base
    res_after_d1 = link.down_residual
    new2 = _model(2, scale=0.5)
    d2 = link.encode_down(new2)                  # rewrites the residual
    link.restore_downlink(d2)                    # ...fetch cancelled
    assert link.acked_base is acked              # ack did not advance
    assert _vec_err(link.down_residual, res_after_d1) == 0.0
    # re-dispatch after the cancel: the worker still decodes correctly
    # against the unmoved acked base, and the delivered reconstruction
    # plus the new residual carry exactly the full outstanding delta
    d3 = link.encode_down(new2)
    vec = link.decode_down_vec(d3)
    full = t.bundle.pack(new2) - acked
    assert _vec_err(vec - acked + link.down_residual, full) < 1e-5


def test_downlink_restore_ignores_non_pending_payload():
    t, link, d1, base, new = _ack_roundtrip("topk_ef")
    res = link.down_residual
    link.complete_fetch(d1)                      # d1 acked: no longer pending
    link.restore_downlink(d1)                    # stale restore: no-op
    assert link.down_residual is res
    assert link.acked_base is not None


def test_downlink_tracking_error_stays_bounded():
    """The downlink is self-correcting: each dispatch's delta vs the
    worker's ACTUAL acked state re-carries all previously dropped mass,
    so the worker's reconstruction deficit must stay bounded at the
    single-dispatch compression error over many rounds of small server
    updates (an implementation that re-adds the residual to the encode
    input double-counts the deficit and diverges — regression guard),
    and ``down_residual`` must equal the deficit exactly."""
    base = _model(0)
    for codec in ("topk_ef", "topk_ef+int8"):
        t = transport.Transport(base, codec="raw", down_codec=codec,
                                frac=0.2)
        link = t.link("w0")
        link.complete_fetch(link.encode_down(base))
        cur = base
        errs = []
        for i in range(40):
            cur = jax.tree.map(
                lambda l, k=i: l + 0.01 * jax.random.normal(
                    jax.random.PRNGKey(200 + k), l.shape), cur)
            link.complete_fetch(link.encode_down(cur))
            deficit = t.bundle.pack(cur) - link.acked_base
            assert _vec_err(deficit, link.down_residual) < 1e-5
            errs.append(float(jnp.max(jnp.abs(deficit))))
        # bounded, not growing: the tail is no worse than the early error
        assert max(errs) < 0.1, (codec, max(errs))
        assert max(errs[-10:]) <= 2.0 * max(errs[:10]), (codec, errs)


def test_symmetric_uplink_decodes_against_lossy_downlink_base():
    """With compression both ways the uplink delta must be based on the
    (lossy) model the worker actually fetched, not the exact server
    model — tx_base is the downlink reconstruction."""
    base = _model(0)
    t = transport.Transport(base, codec="topk_ef+int8",
                            frac=0.1)            # symmetric by default
    assert t.codec == t.down_codec == "topk_ef+int8"
    link = t.link("w0")
    link.complete_fetch(link.encode_down(base))
    d = link.encode_down(_model(1, scale=0.5))
    fetched = link.complete_fetch(d)             # lossy reconstruction
    assert _vec_err(t.bundle.pack(fetched), link.tx_base) == 0.0
    trained = jax.tree.map(lambda l: l + 0.01, fetched)
    up = link.encode_up(trained)
    got = link.decode_up_vec(up)
    want = t.bundle.pack(trained)
    # one EF step: reconstruction + residual == the true uplink delta
    assert _vec_err(got + link.residual, want) < 1e-5


# ---------------- expected bytes / selection pricing ----------------

def test_expected_down_bytes_follow_down_codec():
    base = _model(0)
    n = N_PARAMS
    cases = {
        "raw": 4 * n,
        "delta": 4 * n,
        "int8": n + 4,
        "topk_ef": transport.bitmap_bytes(n) + 4 * transport.topk_k(n, 0.1),
        "topk_ef+int8": (transport.bitmap_bytes(n) + 4
                         + transport.topk_k(n, 0.1)),
    }
    for codec, want in cases.items():
        t = transport.Transport(base, codec="raw", down_codec=codec,
                                frac=0.1)
        assert t.expected_down_bytes() == want, codec
        # and the actual steady-state payload matches the estimate for the
        # deterministic codecs
        if codec in ("delta", "int8"):
            _, _, d1, _, _ = _ack_roundtrip(codec)
            assert d1.wire_bytes == want


def test_expected_oneway_bytes_mean_of_directions():
    base = _model(0)
    t = transport.Transport(base, codec="topk_ef+int8", down_codec="raw",
                            frac=0.1)
    assert t.expected_oneway_bytes() == \
        (t.expected_down_bytes() + t.expected_up_bytes()) // 2
    sym = transport.Transport(base, codec="topk_ef+int8", frac=0.1)
    assert sym.expected_down_bytes() == sym.expected_up_bytes()
    assert sym.expected_oneway_bytes() < t.expected_oneway_bytes()


# every codec's expected bytes at N_PARAMS=1000 / frac=0.1 (raw 4000 B):
# bitmap 125 B, k=100 kept
_PRICE = {"raw": 4000, "delta": 4000, "int8": 1004,
          "topk_ef": 525, "topk_ef+int8": 229}
_PAIRINGS = [(u, d) for u in _PRICE for d in _PRICE]


@pytest.mark.parametrize("up,down", _PAIRINGS,
                         ids=[f"up={u}-down={d}" for u, d in _PAIRINGS])
def test_expected_oneway_bytes_every_codec_pairing(up, down):
    """eq-3.4 round pricing pinned for EVERY up x down codec pairing:
    per-direction estimates follow each direction's own codec, and the
    round-trip figure is the floor-average of the two (asymmetric pairs
    with an odd byte sum exercise the floor)."""
    t = transport.Transport(_model(0), codec=up, down_codec=down, frac=0.1)
    assert t.expected_up_bytes() == _PRICE[up]
    assert t.expected_down_bytes() == _PRICE[down]
    assert t.expected_oneway_bytes() == (_PRICE[down] + _PRICE[up]) // 2


def test_selection_admit_reject_every_codec_pairing():
    """Admit/reject decisions of the eq-3.4 budget for every pairing: a
    slow-link worker (1e5 B/s, no training data) against a budget of
    0.025 s admits exactly the pairings whose floor-averaged one-way
    bytes are <= 2500 — including the 2502-byte raw x int8 boundary case
    that floor-averaging puts 2 bytes over."""
    from repro.core.selection import TimeBasedSelector

    est = TimeEstimator()
    slow = WorkerProfile("slow", bandwidth=1e5, n_batches=0)
    base = _model(0)
    for (up, down) in _PAIRINGS:
        t = transport.Transport(base, codec=up, down_codec=down, frac=0.1)
        sel = TimeBasedSelector(est, t.expected_oneway_bytes, r=1, T0=0.025)
        oneway = (_PRICE[down] + _PRICE[up]) // 2
        want = ["slow"] if oneway <= 2500 else []
        assert sel.select([slow]) == want, (up, down)
    # and the auto mode's answers from the same budget: with no link
    # rate known the transport prices dense and the budget rejects;
    # binding a rate flips the estimate to the compressed choice, which
    # admits — the time-varying BytesSpec the selectors must re-resolve
    from repro.core.autotune import AutoPolicy
    auto = transport.Transport(base, codec="auto")
    sel = TimeBasedSelector(est, auto.expected_oneway_bytes, r=1, T0=0.025)
    assert auto.expected_oneway_bytes() == 4000      # nothing known: dense
    assert sel.select([slow]) == []
    auto.tuner.bind_bandwidth(lambda wid: 1e5, lambda: 1e5)
    # topk_ef+int8 at the warmest frac rung (0.1): 125 + 4 + 100
    assert auto.expected_oneway_bytes() == 229
    assert sel.select([slow]) == ["slow"]
    # a forced DGC warmup round prices dense while it lasts
    auto.tuner.policy = AutoPolicy(warmup_rounds=1)
    assert auto.expected_oneway_bytes() == 4000
    assert sel.select([slow]) == []
    auto.note_round(type("P", (), {"accuracy": 0.1})())
    assert auto.expected_oneway_bytes() == 229


def test_selection_time_budget_prices_downlink_codec():
    """The eq-3.4 time budget must shrink when the downlink codec shrinks
    the expected bytes: a slow-link worker admitted under the symmetric
    codec stays excluded under raw."""
    from repro.core.selection import TimeBasedSelector

    est = TimeEstimator()
    slow = WorkerProfile("slow", bandwidth=1e5, n_batches=1)
    base = _model(0)
    raw = transport.Transport(base, codec="raw")
    sym = transport.Transport(base, codec="topk_ef+int8", frac=0.1)
    t_raw = TimeBasedSelector(est, raw.expected_oneway_bytes, r=1, T0=0.0)
    t_sym = TimeBasedSelector(est, sym.expected_oneway_bytes, r=1, T0=0.0)
    # the transmit leg of the budget scales with the codec'd expected bytes
    tt_raw = t_raw._t_total(slow, raw.expected_oneway_bytes()) \
        - est.t_one(slow)
    tt_sym = t_sym._t_total(slow, sym.expected_oneway_bytes()) \
        - est.t_one(slow)
    assert abs(tt_raw - raw.expected_oneway_bytes() / 1e5) < 1e-9
    assert abs(tt_sym - sym.expected_oneway_bytes() / 1e5) < 1e-9
    assert tt_sym < tt_raw / 10
    # budget T between the two admits the worker only under compression
    T = (tt_sym + tt_raw) / 2 + est.t_one(slow)
    t_raw.T = t_sym.T = T
    assert t_sym.select([slow]) == ["slow"]
    assert t_raw.select([slow]) == []


def test_estimator_downlink_estimate_scales_with_codec_bytes():
    """eq-3.4 transmit pricing: with one measured bandwidth sample the
    downlink leg estimate equals expected_down_bytes / bandwidth for
    whichever down codec is configured."""
    est = TimeEstimator()
    p = WorkerProfile("w0", bandwidth=1e9)
    est.observe_transmit("w0", 1.0, 1_000_000)   # 1 MB/s measured
    base = _model(0)
    for codec in ("raw", "int8", "topk_ef+int8"):
        t = transport.Transport(base, codec="raw", down_codec=codec,
                                frac=0.1)
        want = t.expected_down_bytes() / 1e6
        assert abs(est.t_transmit(p, t.expected_down_bytes()) - want) < 1e-12


# ---------------- error feedback across rounds ----------------

@pytest.mark.parametrize("model,frac,steps,scale", [
    (_model(0), 0.2, 12, 0.01),
    ({"g": jnp.zeros((32, 16))}, 0.25, 30, 0.1),
], ids=["two_leaf", "one_leaf"])
def test_link_error_feedback_recovers_mass(model, frac, steps, scale):
    """Cumulative reconstructed deltas + residual == cumulative true deltas
    (the EF contraction property, now per-link)."""
    base = model
    t = transport.Transport(base, codec="topk_ef", frac=frac)
    link = t.link("w0")
    total_in = jnp.zeros((t.bundle.padded_size,))
    total_out = jnp.zeros((t.bundle.padded_size,))
    cur = base
    for i in range(steps):
        link.encode_down(cur)
        new = jax.tree.map(
            lambda l, k=i: l + scale * jax.random.normal(
                jax.random.PRNGKey(100 + k), l.shape), cur)
        up = link.encode_up(new)
        total_in += t.bundle.pack(new) - link.tx_base
        total_out += link.decode_up_vec(up) - link.tx_base
        cur = t.bundle.unpack(link.decode_up_vec(up))
    assert _vec_err(total_in, total_out + link.residual) < 1e-4


def test_compressor_parity_with_flat_codec_single_leaf():
    """A topk_ef / topk_ef+int8 link uplink on a one-leaf tree ==
    ``ef_topk_encode`` on the packed vector with the residual carried by
    hand: same reconstruction, same residual, same wire bytes."""
    base = {"g": jnp.zeros((1000,))}
    deltas = [{"g": jax.random.normal(jax.random.PRNGKey(i), (1000,))}
              for i in range(4)]
    for quantize in (False, True):
        t = transport.Transport(
            base, codec="topk_ef+int8" if quantize else "topk_ef",
            down_codec="raw", frac=0.1)
        link = t.link("w0")
        res_vec = jnp.zeros((t.bundle.padded_size,))
        for d in deltas:
            link.encode_down(base)               # the uplink base: zeros
            up = link.encode_up(d)
            _, recon, res_vec, wire = transport.ef_topk_encode(
                t.bundle.pack(d) + res_vec, n_params=1000, frac=0.1,
                quantize=quantize)
            assert up.wire_bytes == wire
            assert _vec_err(link.decode_up_vec(up) - link.tx_base,
                            recon) < 1e-6
        assert _vec_err(link.residual, res_vec) < 1e-6


# ---------------- estimator: measured bandwidth ----------------

def test_estimator_learns_bandwidth_not_fixed_time():
    est = TimeEstimator()
    p = WorkerProfile("w0", bandwidth=10e6)
    est.observe_transmit("w0", 0.5, 5_000_000)       # 10 MB/s measured
    assert abs(est.t_transmit(p, 5_000_000) - 0.5) < 1e-12
    # the estimate must SCALE with payload size (the pre-fix bug returned
    # the fixed measured time for any requested size)
    assert abs(est.t_transmit(p, 500_000) - 0.05) < 1e-12
    assert abs(est.bandwidth("w0") - 10e6) < 1e-3
    assert est.bandwidth("nobody") is None


# ---------------- warehouse ticket hygiene ----------------

def test_redeem_deletes_stored_object():
    wh = DataWarehouse()
    uid = wh.put({"x": 1})
    cred = wh.issue_ticket(uid)
    assert wh.redeem_ticket(cred) == {"x": 1}
    assert uid not in wh                     # hand-off: source copy freed


def test_revoke_and_drop_tickets():
    wh = DataWarehouse()
    creds = [wh.issue_ticket(wh.put(i)) for i in range(3)]
    wh.revoke_ticket(creds[0])
    with pytest.raises(KeyError):
        wh.redeem_ticket(creds[0])
    wh.drop_tickets()
    assert not wh._tickets and not wh._meta


# ---------------- end-to-end byte accounting ----------------

def _mini_setup():
    return make_setup(TABLE_4_1["mnist_even"], seed=0, noise=0.25,
                      batch_size=32, het="strong")


def test_history_byte_counters_raw_exact():
    setup = _mini_setup()
    h = run_fl(setup, mode="async", selector="all", epochs_per_round=5,
               max_rounds=5, transport="raw")
    mb = setup.model_bytes
    # every response costs exactly model_bytes up; dispatches cost it down
    assert h[-1].up_bytes % mb == 0 and h[-1].up_bytes >= 5 * mb
    assert h[-1].down_bytes % mb == 0
    assert h[-1].down_bytes >= h[-1].up_bytes     # re-dispatch >= responses
    ups = [p.up_bytes for p in h]
    assert ups == sorted(ups)                     # cumulative, monotone


def test_sync_stale_response_redeemed_not_leaked():
    """Sync mode must redeem (and free) tickets of responses it ignores."""
    setup = _mini_setup()
    from repro.core.events import EventLoop
    from repro.core.selection import make_selector
    from repro.core.server import AggregationServer
    from repro.core.worker import FLWorker

    loop = EventLoop()
    est = TimeEstimator(server_freq=3.0, t_onebatch_server=0.05)
    server = AggregationServer(
        weights=setup.weights0, loop=loop, estimator=est,
        selector=make_selector("all", est, setup.model_bytes),
        eval_fn=setup.eval_fn, model_bytes=setup.model_bytes, mode="sync",
        epochs_per_round=2, max_rounds=2)
    for prof, shard in zip(setup.profiles, setup.shards):
        server.add_worker(FLWorker(prof.worker_id, profile=prof, data=shard,
                                   train_fn=setup.train_fn, loop=loop))
    server.start()
    loop.run(max_events=50_000)
    for w in server.workers.values():
        assert not w.warehouse._tickets, "unredeemed ticket leaked"
        assert not w.warehouse._meta, "stored weights leaked"


def test_uplink_bytes_ratio_at_least_10x():
    """ISSUE acceptance: topk_ef+int8 at frac=0.1 ships >= 10x fewer
    cumulative uplink bytes than raw per response."""
    setup = _mini_setup()
    hr = run_fl(setup, mode="async", selector="all", epochs_per_round=5,
                max_rounds=6, transport="raw")
    hc = run_fl(_mini_setup(), mode="async", selector="all",
                epochs_per_round=5, max_rounds=6, transport="topk_ef+int8",
                transport_down="raw", transport_frac=0.1)
    per_resp_raw = hr[-1].up_bytes / hr[-1].version
    per_resp_c = hc[-1].up_bytes / hc[-1].version
    assert per_resp_raw >= 10 * per_resp_c
    # uplink-only config: the model still goes down in full every dispatch
    assert hc[-1].down_bytes == hr[-1].down_bytes


def test_downlink_bytes_ratio_at_least_10x_steady_state():
    """ISSUE acceptance: the symmetric codec ships >= 10x fewer downlink
    bytes than raw once past first-contact (each worker's first dispatch
    is the raw fallback — no acked base yet — so the ratio is measured on
    the marginal bytes between two later history points)."""
    hr = run_fl(_mini_setup(), mode="async", selector="all",
                epochs_per_round=5, max_rounds=14, transport="raw")
    hc = run_fl(_mini_setup(), mode="async", selector="all",
                epochs_per_round=5, max_rounds=14, transport="topk_ef+int8",
                transport_frac=0.1)

    def marginal_down(h):
        return (h[-1].down_bytes - h[4].down_bytes) / \
            (h[-1].version - h[4].version)

    assert marginal_down(hr) >= 10 * marginal_down(hc)
    # and cumulative downlink is already well below raw despite the
    # 10 first-contact raw dispatches
    assert hc[-1].down_bytes < hr[-1].down_bytes
    # uplink compression unchanged by the downlink codec
    assert (hr[-1].up_bytes / hr[-1].version
            >= 10 * hc[-1].up_bytes / hc[-1].version)


def test_byte_counters_equal_sum_of_payload_wire_bytes():
    """ISSUE satellite: the cumulative HistoryPoint counters must equal
    the sum of the actual payloads' wire_bytes — down over every encoded
    dispatch, up over every response the server received — including a
    worker dying mid-round (its encoded response is never delivered nor
    counted)."""
    from repro.core.events import EventLoop
    from repro.core.selection import make_selector
    from repro.core.server import AggregationServer
    from repro.core.worker import FLWorker

    setup = _mini_setup()
    loop = EventLoop()
    est = TimeEstimator(server_freq=3.0, t_onebatch_server=0.05)
    tr = transport.Transport(setup.weights0, codec="topk_ef+int8",
                             frac=0.1, raw_bytes=setup.model_bytes)
    sent_down, delivered_up = [], []
    orig_link = tr.link

    def spying_link(wid):
        l = orig_link(wid)
        if not getattr(l, "_spied", False):
            l._spied = True
            orig_enc = l.encode_down
            l.encode_down = lambda w: _spy(orig_enc(w))
        return l

    def _spy(payload):
        sent_down.append(payload.wire_bytes)
        return payload

    tr.link = spying_link
    server = AggregationServer(
        weights=setup.weights0, loop=loop, estimator=est,
        selector=make_selector("all", est, tr.expected_oneway_bytes),
        eval_fn=setup.eval_fn, model_bytes=setup.model_bytes, mode="async",
        epochs_per_round=3, max_rounds=8, transport=tr)
    orig_resp = server._on_response

    def spying_response(res):
        if not server.done:
            delivered_up.append(res.up_bytes)
        orig_resp(res)

    server._on_response = spying_response
    for prof, shard in zip(setup.profiles, setup.shards):
        server.add_worker(FLWorker(prof.worker_id, profile=prof, data=shard,
                                   train_fn=setup.train_fn, loop=loop))
    # one worker dies mid-run: whatever it is doing (fetch, train, or
    # respond) must not corrupt the byte accounting
    loop.schedule(0.2, lambda: setattr(
        server.workers["w3"].profile, "failed", True))
    server.start()
    loop.run(max_events=100_000)
    h = server.history
    assert h[-1].down_bytes == sum(sent_down) == server.total_down_bytes
    assert h[-1].up_bytes == sum(delivered_up) == server.total_up_bytes
    # the counters are cumulative and monotone along the history
    for prev, cur in zip(h, h[1:]):
        assert cur.up_bytes >= prev.up_bytes
        assert cur.down_bytes >= prev.down_bytes


def test_cancelled_fetch_does_not_advance_ack():
    """A round closing while the downlink fetch is still in flight must
    cancel it without advancing the last-acked base or losing EF state;
    a re-dispatch afterwards still starts from the raw fallback."""
    from repro.core.events import EventLoop
    from repro.core.warehouse import Pointer
    from repro.core.worker import FLWorker

    base = _model(0)
    loop = EventLoop()
    prof = WorkerProfile("w0", bandwidth=1e3, n_batches=1)   # slow fetch
    w = FLWorker("w0", profile=prof,
                 data={"x": np.zeros((4, 4)), "y": np.zeros((4,))},
                 train_fn=lambda p, x, y, e: jax.tree.map(
                     lambda l: l + 0.01, p), loop=loop)
    t = transport.Transport(base, codec="topk_ef+int8", frac=0.1)
    link = t.link("w0")
    ptr = Pointer("server://a", "m")
    w.add_server(ptr)
    delivered = []
    down = link.encode_down(base)
    w.train_async(ptr, down, 0, 1, link, delivered.append)
    assert w._fetching, "fetch should be in flight"
    # round closes mid-fetch
    w.cancel_inflight(ptr)
    assert not w._fetching and not w.busy
    assert link.acked_base is None               # ack did NOT advance
    loop.run()                                   # dead fetch event: no-op
    assert delivered == [] and link.acked_base is None
    # re-dispatch: still no acked base -> raw fallback again, and the
    # whole chain completes normally now
    d2 = link.encode_down(base)
    assert d2.codec == "raw"
    w.train_async(ptr, d2, 0, 1, link, delivered.append)
    loop.run()
    assert len(delivered) == 1
    assert link.acked_base is not None           # acked at fetch complete


def test_mid_transmit_death_keeps_fetch_ack():
    """A worker that dies while its response is in transit DID complete
    its fetch: the explicit fetch-complete event advanced the ack, so the
    server may keep encoding downlink deltas against that base even
    though the response never arrives (and its uplink EF mass is credited
    back)."""
    from repro.core.events import EventLoop
    from repro.core.warehouse import Pointer
    from repro.core.worker import FLWorker

    base = _model(0)
    loop = EventLoop()
    prof = WorkerProfile("w0", bandwidth=1e6, n_batches=1)
    w = FLWorker("w0", profile=prof,
                 data={"x": np.zeros((4, 4)), "y": np.zeros((4,))},
                 train_fn=lambda p, x, y, e: jax.tree.map(
                     lambda l: l + 0.01, p), loop=loop)
    t = transport.Transport(base, codec="topk_ef+int8", frac=0.1)
    link = t.link("w0")
    ptr = Pointer("server://a", "m")
    w.add_server(ptr)
    delivered = []
    w.train_async(ptr, link.encode_down(base), 0, 1, link, delivered.append)
    # run past fetch + train so the uplink is in flight
    loop.run(until=w.true_t_transmit(t.raw_bytes) + w.true_t_one() + 1e-9)
    assert w._inflight, "uplink should be in flight"
    acked = link.acked_base
    assert acked is not None                     # fetch completed -> acked
    w.profile.failed = True                      # dies mid-transmit
    loop.run()
    assert delivered == []
    assert link.acked_base is acked              # the ack survives death


def test_restore_uplink_returns_ef_mass():
    """A cancelled/discarded uplink must credit its reconstruction back
    into the EF residual: residual_after_restore == delta + residual_before
    (nothing is lost from the error-feedback contract)."""
    base = _model(0)
    for codec in ("topk_ef", "topk_ef+int8"):
        t = transport.Transport(base, codec=codec, frac=0.1)
        link = t.link("w0")
        link.encode_down(base)
        new = _model(1, scale=0.5)
        up1 = link.encode_up(new)            # round 1 establishes residual
        res_before = link.residual
        delta = t.bundle.pack(_model(2, scale=0.5)) - link.tx_base
        up2 = link.encode_up(t.bundle.unpack(delta + link.tx_base))
        link.restore_uplink(up2)
        assert _vec_err(link.residual, delta + res_before) < 1e-5


def test_cancelled_transfer_after_recovery_does_not_crash():
    """A server cancels an in-flight two-stage (top-k) transfer at round
    close and the worker recovers (failed=False) before its _send event
    fires: the stale send must drop silently — delivering the revoked
    ticket would crash redeem_ticket with a KeyError."""
    from repro.core.events import EventLoop
    from repro.core.worker import FLWorker

    base = _model(0)
    loop = EventLoop()
    prof = WorkerProfile("w0", bandwidth=1e6, n_batches=1)
    w = FLWorker("w0", profile=prof,
                 data={"x": np.zeros((4, 4)), "y": np.zeros((4,))},
                 train_fn=lambda p, x, y, e: jax.tree.map(
                     lambda l: l + 0.01, p), loop=loop)
    t = transport.Transport(base, codec="topk_ef+int8", frac=0.1)
    link = t.link("w0")
    from repro.core.warehouse import Pointer
    ptr = Pointer("server://a", "m")
    w.add_server(ptr)
    delivered = []
    w.train_async(ptr, link.encode_down(base), 0, 1, link, delivered.append)
    # run just past train-end so the uplink is in flight (ticket issued)...
    loop.run(until=w.true_t_transmit(t.raw_bytes) + w.true_t_one() + 1e-9)
    assert w._inflight, "transfer should be in flight"
    # ...then the round closes (cancel) and the worker later recovers
    w.profile.failed = True
    w.cancel_inflight(ptr)
    w.profile.failed = False
    loop.run()                                  # fires _send: must not raise
    assert delivered == []                      # cancelled, never delivered
    assert not w._inflight and not w.warehouse._tickets
    assert not w.warehouse._meta, "cancelled payload leaked"


def test_cancel_inflight_scoped_to_one_server():
    """cancel_inflight must revoke only the calling server's transfer,
    leaving another server's ticket in the same warehouse intact."""
    from repro.core.events import EventLoop
    from repro.core.warehouse import Pointer
    from repro.core.worker import FLWorker
    from repro.core.estimator import WorkerProfile

    w = FLWorker("w0", profile=WorkerProfile("w0"), data={"x": [], "y": []},
                 train_fn=None, loop=EventLoop())
    base = _model(0)
    tA = transport.Transport(base, codec="topk_ef", frac=0.1)
    linkA, linkB = tA.link("w0"), tA.link("w0-b")
    linkA.encode_down(base)
    linkB.encode_down(base)
    upA, upB = linkA.encode_up(_model(1)), linkB.encode_up(_model(2))
    tickA = w.warehouse.issue_ticket(w.warehouse.put(upA))
    tickB = w.warehouse.issue_ticket(w.warehouse.put(upB))
    ptrA, ptrB = Pointer("server://a", "m"), Pointer("server://b", "m")
    w._inflight[ptrA] = (tickA, upA, linkA)
    w._inflight[ptrB] = (tickB, upB, linkB)
    w.cancel_inflight(ptrA)
    assert not w.warehouse.has_ticket(tickA)
    assert w.warehouse.has_ticket(tickB)        # other server untouched
    assert w.warehouse.redeem_ticket(tickB) is upB


def test_bandwidth_starved_t80_compressed_beats_raw():
    """ISSUE acceptance: on a bandwidth-starved edge profile the codec'd
    transport reaches 80% accuracy in less simulated time than raw, the
    symmetric codec is no worse than uplink-only compression, and it
    ships >= 10x fewer steady-state downlink bytes than raw."""
    def starved(codec, down=None):
        setup = make_setup(TABLE_4_1["mnist_even"], seed=0, noise=0.2,
                           batch_size=64, het="strong")
        for p in setup.profiles:
            p.bandwidth /= 2000.0
        return run_fl(setup, mode="async", selector="time_based",
                      aggregator="linear", epochs_per_round=10,
                      max_rounds=900,
                      selector_kw={"r": 10, "T0": 0.0, "A": 0.01},
                      async_latest_table=False, async_alpha=0.9,
                      async_stale_pow=0.25, transport=codec,
                      transport_down=down, target_accuracy=0.81)
    h_raw = starved("raw")
    h_up = starved("topk_ef+int8", "raw")       # PR-2-era uplink-only
    h_sym = starved("topk_ef+int8")             # symmetric (default)
    t_raw = time_to_accuracy(h_raw, 0.8)
    t_up = time_to_accuracy(h_up, 0.8)
    t_sym = time_to_accuracy(h_sym, 0.8)
    assert t_raw is not None and t_up is not None and t_sym is not None
    assert t_up < t_raw, (t_up, t_raw)
    assert t_sym <= t_up, (t_sym, t_up)         # downlink codec: no worse

    def marginal_down(h):                       # past first-contact raws
        return (h[-1].down_bytes - h[10].down_bytes) / \
            (h[-1].version - h[10].version)

    assert marginal_down(h_raw) >= 10 * marginal_down(h_sym)
