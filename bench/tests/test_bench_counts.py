"""The operation and byte counts of the benchmark against hand counts."""
import json
from pathlib import Path

import pytest

from bench import costs
from bench.harness import load_module

BENCH = Path(__file__).resolve().parents[1]
CNN = load_module(BENCH / "families" / "cnn.py")


# the shapes src/repro/configs/paper_cnn.py gives its CIFAR CNN: no cell
# runs it, but the family's counts hold for any input
CIFAR_SHAPES = {"image_hw": 32, "channels": 3, "conv1": 16, "conv2": 32,
                "n_classes": 10, "n_params": 34_538}


def _config(name):
    if name == "cifar-shapes":
        return CIFAR_SHAPES
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


# (config, forward FLOP per image, parameters), counted by hand:
# MNIST  conv1 28*28*16*25*1*2 = 627,200   conv2 14*14*32*25*16*2 = 5,017,600
#        dense 1568*10*2 = 31,360
# CIFAR  conv1 32*32*16*25*3*2 = 2,457,600 conv2 16*16*32*25*16*2 = 6,553,600
#        dense 2048*10*2 = 40,960
HAND = [("thesis-mnist-cnn", 5_676_160, 627_200, 28_938),
        ("cifar-shapes", 9_052_160, 2_457_600, 34_538)]


@pytest.mark.parametrize("name,forward,conv1,params", HAND)
def test_forward_and_training_counts(name, forward, conv1, params):
    cfg = _config(name)
    assert CNN.forward_flops(cfg) == forward
    assert CNN.layer_flops(cfg)["conv1"] == conv1
    assert CNN.n_params(cfg) == params == cfg["n_params"]
    # forward + weight gradients + input gradients of all but the first
    assert CNN.train_flops(cfg, 32, 10) == (3 * forward - conv1) * 32 * 10


@pytest.mark.parametrize("name,forward,conv1,params", HAND)
def test_parameter_count_matches_the_weights(name, forward, conv1, params):
    import jax
    w = CNN.init_weights(jax.random.PRNGKey(0), _config(name))
    assert sum(int(v.size) for v in w.values()) == params


def test_training_bytes_by_hand():
    cfg = _config("thesis-mnist-cnn")
    # 32 images of 28*28 f32 and an int32 label, parameters in and out
    assert CNN.train_bytes(cfg, 32, 10) == 32 * (784 * 4 + 4) + 8 * 28_938


def test_server_pass_counts_by_hand():
    n = 1000
    assert costs.merge(n, 100, False) == (200_000, 101 * 4000)
    assert costs.merge(n, 1, True) == (4000, 3 * 4000)
    assert costs.adam_step(n)[1] == 28_000
    assert costs.codec_encode(n)[1] == 9000
    assert costs.codec_decode(n)[1] == 9000
    assert costs.codec_threshold(n) == (1000, 4000)
    assert costs.codec_dequant(n) == (1000, 5000)
    peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    assert costs.least_seconds(2e12, 1e6, peaks) == 2.0
    assert costs.least_seconds(1e6, 3e9, peaks) == 3.0
