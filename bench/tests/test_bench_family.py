"""A second model family through the harness, on the CPU: token ids in, a
nested parameter tree, plain SGD (``fixtures/toy_family.py``).  Nothing
of the harness is particular to the CNN: its files, data, trees and
training programs come from the family, and ``correct`` holds for a sound
run and fails for a training that takes half its step."""
import copy
from pathlib import Path

import pytest

from bench import check, device, harness

FIXTURES = Path(__file__).resolve().parent / "fixtures"
TOY = harness.load_module(FIXTURES / "toy_family.py")
CONFIG = {"family": "toy", "vocab": 97, "dim": 16, "seq_len": 6,
          "n_classes": 5, "lr": 0.5}
TRAFFIC = {"workers": 12, "batches_each": 1, "batch_size": 16, "n_test": 64,
           "het": "mixed", "per_batch_server": 0.05,
           "run": {"mode": "sync", "selector": "all", "cohort": 4,
                   "epochs_per_round": 3, "transport": "raw",
                   "cohort_seed": 0},
           "check_versions": 3,
           "warmup": {"min_versions": 3, "quiet_versions": 2}}
# the program and the reference both train in float32 on the CPU
LIMITS = {"train_gap": 1e-3, "version_gap": 1e-3, "acc_gap": 0.05}


def toy_cell(config=CONFIG, traffic=TRAFFIC, limits=LIMITS) -> harness.Cell:
    return harness.Cell(name="toy", chips=1, config=dict(config),
                        family=TOY, traffic=copy.deepcopy(traffic),
                        limits=dict(limits), end_to_end=[], per_layer=[])


def half_step(setup, tr):
    """Each training moves the weights half as far as it should."""
    import jax
    prog = setup.train_fn

    def fn(params, x, y, epochs):
        out = prog(params, x, y, epochs)
        return jax.tree.map(lambda a, b: a + 0.5 * (b - a), params, out)
    return {"train_fn": fn}


@pytest.mark.parametrize("fault", [None, "half_step"])
def test_a_second_family_through_the_harness(fault):
    cell = toy_cell()
    log = device.CompileLog()
    b = harness.build(cell, 2 ** 33 + 9, half_step if fault else None)
    assert b.weights0["embed"]["table"].shape == (97, 16)
    assert b.traffic.shards[0]["x"].shape == (16, 6)
    harness.precompile(b)
    cap = harness.warm_up(b, log)
    win = harness.measure(b, 0.2, log)
    assert win["updates"] > 0 and win["window_compiles"] == 0
    tr = b.traffic
    del b
    numbers = harness.reference_numbers(cell, cap, tr)
    assert check.judge(numbers, cell.limits) is (fault is None), numbers
    # the trees are named by path
    assert set(cap.trains[0].norms) == {"['embed']['table']",
                                        "['head']['b']", "['head']['w']"}


def test_tree_paths_flatten_and_back():
    import numpy as np
    tree = {"head": {"w": np.arange(6.0).reshape(2, 3), "b": np.ones(3)},
            "embed": {"table": np.full((2, 2), 7.0)}}
    names = [k for k, _ in check.leaves(tree)]
    assert names == ["['embed']['table']", "['head']['b']", "['head']['w']"]
    vec = check.flatten(tree)
    assert vec.dtype == np.float64 and vec.tolist() == (
        [7.0] * 4 + [1.0] * 3 + list(range(6)))
    back = check.unflatten(vec, tree)
    assert back["head"]["w"].shape == (2, 3)
    assert all(np.array_equal(a, b) for (_, a), (_, b) in
               zip(check.leaves(back), check.leaves(tree)))
