"""What the check holds of a model, measured on the CPU at about 4e6
parameters (the toy family's nested tree), a cohort of 8 and 3 compared
versions: the capture keeps two models on the device once warm-up is
over, and the replay's host peak stays under six float64 models however
many rows and versions it follows."""
import gc
import tracemalloc

import pytest

from bench import device, harness
from bench.tests.test_bench_family import CONFIG, TRAFFIC, toy_cell

BIG = dict(CONFIG, vocab=3900, dim=1024, n_classes=8, seq_len=4, lr=0.1)
COHORT = dict(TRAFFIC, workers=16, batch_size=8, n_test=32,
              run=dict(TRAFFIC["run"], cohort=8, epochs_per_round=1),
              warmup={"min_versions": 3, "quiet_versions": 1})
# beyond six float64 models: one float32 copy (the reference's input on
# its way to the device, or the program's version read back), and 1 MiB
# of small arrays and bookkeeping
SLACK_F32_MODELS = 1


@pytest.fixture(scope="module")
def warmed():
    cell = toy_cell(BIG, COHORT)
    b = harness.build(cell, 12345)
    harness.precompile(b)
    cap = harness.warm_up(b, device.CompileLog())
    tr = b.traffic
    del b
    gc.collect()
    return cell, cap, tr


def test_capture_keeps_two_models_on_the_device(warmed):
    import jax
    cell, cap, _ = warmed
    assert cell.family.n_params(cell.config) > 3.9e6
    assert [v.index for v in cap.versions if v.weights is not None] == [0, 3]
    assert len(cap.trains) == 3 * 8
    # with the federation gone, every table alive is one the capture holds
    table = (BIG["vocab"], BIG["dim"])
    assert sum(a.shape == table for a in jax.live_arrays()) <= 2


def test_replay_host_peak_is_six_models(warmed):
    cell, cap, tr = warmed
    n = cell.family.n_params(cell.config)
    tracemalloc.start()
    try:
        numbers = harness.reference_numbers(cell, cap, tr)
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    assert all(v is not None for v in numbers.values())
    bound = 6 * 8 * n + SLACK_F32_MODELS * 4 * n + 2 ** 20
    assert peak <= bound, (peak / (8 * n), "float64 models")
