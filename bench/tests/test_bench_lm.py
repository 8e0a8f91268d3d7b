"""The language-model family (``families/lm.py``) through the harness on
the CPU, at a toy size of its configuration's shape: ``correct`` holds for
the program's own training and fails for a training that moves the
weights half as far; its counts and its configuration are the program's
Mellum2 share."""
import json
from pathlib import Path

import pytest

from bench import check, device, harness

BENCH = Path(__file__).resolve().parents[1]
CELL = "mellum2-xsilo-sync-raw"
LM = harness.load_module(BENCH / "families" / "lm.py")
CONFIG = json.loads((BENCH / "configs" / "mellum2-12b-a2.5b-ep8.json")
                    .read_text())
# the cell's layer period, router and held experts at widths a CPU trains
# in seconds: 4 layers (3 windowed of 8, 1 full with YaRN), 2 of 8
# experts held with 2 per token, 97 ids, sequences of 32 tokens
TOY = {"config": {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 2, "experts_held": 2, "expert_offset": 2,
    "vocab_size": 97, "sliding_window": 8, "seq_len": 32, "loss_chunk": 16,
    "microbatches": 2, "lr": 0.5,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                           "factor": 4, "original_max_position_embeddings": 16,
                           "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.1386294361119891},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}}}}


def half_step(setup, tr):
    """Each training moves the weights half as far as it should."""
    import jax
    prog = setup.train_fn

    def fn(params, x, y, epochs):
        out = prog(params, x, y, epochs)
        return jax.tree.map(lambda a, b: a + 0.5 * (b - a), params, out)
    return {"train_fn": fn}


@pytest.mark.parametrize("fault", [None, "half_step"])
def test_the_share_federates_through_the_harness(fault):
    cell = harness.load_cell(CELL, TOY)
    log = device.CompileLog()
    b = harness.build(cell, 2 ** 33 + 5, half_step if fault else None)
    assert b.traffic.shards[0]["x"].shape == (4, 32)
    assert b.weights0["blocks"]["moe"]["wi_gate"].shape == (1, 4, 2, 64, 32)
    harness.precompile(b)
    cap = harness.warm_up(b, log)
    win = harness.measure(b, 0.2, log)
    assert win["updates"] > 0 and win["window_compiles"] == 0
    tr = b.traffic
    del b
    numbers = harness.reference_numbers(cell, cap, tr)
    assert check.judge(numbers, cell.limits) is (fault is None), numbers
    if fault:
        # half the step: each leaf's change is half the reference's
        assert numbers["train_gap"] == pytest.approx(0.5, abs=0.1)


def test_the_data_is_a_seeded_chain_of_few_successors():
    cfg = {**CONFIG, **TOY["config"]}
    x, y = LM.dataset(6, cfg, {}, 2 ** 40 + 3)
    x2, y2 = LM.dataset(6, cfg, {}, 2 ** 40 + 3)
    assert (x == x2).all() and (y == y2).all()
    assert x.shape == y.shape == (6, 32) and (x[:, 1:] == y[:, :-1]).all()
    succ = {}
    for a, b in zip(x.ravel(), y.ravel()):
        succ.setdefault(int(a), set()).add(int(b))
    assert max(len(s) for s in succ.values()) <= LM.SUCCESSORS


def test_the_configuration_is_the_programs_share():
    from repro.configs.mellum2_12b_a2p5b import SHARE
    mcfg = LM.program_config(CONFIG)
    for f in ("n_layers", "d_model", "n_heads", "n_kv_heads", "hd", "d_ff",
              "vocab_size", "rope_theta", "period", "rope_yarn", "n_experts",
              "top_k", "experts_held", "tie_embeddings"):
        assert getattr(mcfg, f) == getattr(SHARE, f), f
    assert LM.n_params(CONFIG) == mcfg.n_params() == CONFIG["n_params"] \
        == 340_349_184


def test_training_counts_by_hand():
    # forward per token: projections 4 x 42,467,328, router 4 x 294,912,
    # one routed expert row 4 x 12,386,304, windowed scores 3 x 4 x 32 x
    # 128 x 960.0625 keys, full 4 x 32 x 128 x 4,096.5 keys, head
    # 2 x 2,304 x 12,288
    per_token = (4 * 42_467_328 + 4 * 294_912 + 4 * 12_386_304
                 + 3 * 16_384 * 960.0625 + 16_384 * 4_096.5 + 56_623_104)
    assert LM.forward_flops_per_token(CONFIG) == pytest.approx(per_token)
    assert LM.train_flops(CONFIG, 4, 2) == int(3 * per_token * 4 * 8192 * 2)
    assert LM.train_bytes(CONFIG, 4, 2) == 4 * 8192 * 8 + 8 * 340_349_184
