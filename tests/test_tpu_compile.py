"""Compile-only checks of the main path's Pallas kernels for a TPU v5e.

Each test compiles one kernel at a real width for a described ``v5e:2x2``
topology (no chip attached) through the TPU compiler, with
``interpret=False`` given explicitly since the backend here is the CPU,
and asserts that the kernel reached it as a Mosaic ``tpu_custom_call``.
What the chip's compiler refuses (unaligned tiles, too much VMEM, a
program that does not fit HBM, a kernel that cannot be partitioned)
fails here.  Nothing runs: results and times need a chip.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and the tests must collect the same way on every pytest-xdist worker.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.kernels import fedavg_agg, topk_quant
from repro.parallel.sharding import AGG_AXIS

N = 16_777_216            # 16.8M parameters, the mlp_16m width


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                    # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("W", [3, 64])
def test_fedavg_mix_flat_compiles(one_chip, W):
    def mix(stacked, weights, server, scale):
        return fedavg_agg.fedavg_mix_flat(stacked, weights, server, scale,
                                          interpret=False)
    _assert_kernel(mix, _spec((W, N), one_chip), _spec((W,), one_chip),
                   _spec((N,), one_chip), _spec((), one_chip))


def test_fedavg_agg_flat_compiles(one_chip):
    W = 64

    def agg(stacked, weights):
        return fedavg_agg.fedavg_agg_flat(stacked, weights, interpret=False)
    _assert_kernel(agg, _spec((W, N), one_chip), _spec((W,), one_chip))


def test_topk_quant_encode_compiles(one_chip):
    def enc(x, thresh, scale):
        return topk_quant.topk_quant_encode(x, thresh, scale,
                                            use_pallas=True, interpret=False)
    _assert_kernel(enc, _spec((N,), one_chip), _spec((), one_chip),
                   _spec((), one_chip))


def test_dequant_add_compiles(one_chip):
    def dec(q, scale, base):
        return topk_quant.dequant_add(q, scale, base,
                                      use_pallas=True, interpret=False)
    _assert_kernel(dec, _spec((N,), one_chip, jnp.int8),
                   _spec((), one_chip), _spec((N,), one_chip))


@pytest.mark.parametrize("adam", [True, False], ids=["adam", "momentum"])
def test_server_opt_step_flat_compiles(one_chip, adam):
    vec = _spec((N,), one_chip)

    def step(prev, merged, m, v, scalars):
        return fedavg_agg.server_opt_step_flat(prev, merged, m, v, scalars,
                                               adam=adam, interpret=False)
    v = vec if adam else None
    _assert_kernel(step, vec, vec, vec, v,
                   _spec((6 if adam else 4,), one_chip))


def test_fedavg_mix_flat_sharded_compiles(topo):
    mesh = Mesh(np.asarray(topo.devices).reshape(4), (AGG_AXIS,))
    W = 64

    def mix(stacked, weights, server, scale):
        return fedavg_agg.fedavg_mix_flat_sharded(
            stacked, weights, server, scale, mesh=mesh, axis=AGG_AXIS,
            interpret=False)
    rep = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P(None, AGG_AXIS))
    vec = NamedSharding(mesh, P(AGG_AXIS))
    _assert_kernel(mix, _spec((W, N), rows), _spec((W,), rep),
                   _spec((N,), vec), _spec((), rep))
