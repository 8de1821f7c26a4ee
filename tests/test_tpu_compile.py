"""Mosaic compiles of the aggregation kernels for a described TPU v5e chip.

Every other kernel test runs the Pallas interpreter (``interpret=True``) or
the XLA oracles, which accept block shapes and VMEM footprints the chip's
compiler refuses.  These tests compile each kernel of the FL round with
``interpret=False`` for one chip of a described ``v5e:2x2`` topology — no
chip needed — at the widths the engine runs: N = 55,050 (the paper's
784-64-64-10 MLP), K = 20 dense, K = 1024 streamed in K-blocks of 128, and
K = 4096 dense for the VMEM-sized N-block.  Nothing runs, so these say
nothing about values; the interpret-mode tests do.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and pytest workers import every module.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

N = 55_050   # parameters of the 784-64-64-10 MLP


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def compiled_text(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_grad_norm(one_chip):
    text = compiled_text(lambda x: ops.grad_norm(x, interpret=False),
                         one_chip, (N,))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k,k_block", [
    (20, None),      # dense: one (device, row-block) tile per grid step
    (1024, 128),     # streamed: in-kernel accumulation over row blocks
    (12, 3),         # streamed, K-blocks zero-padded to 8 rows
])
def test_batched_moments(one_chip, k, k_block):
    text = compiled_text(
        lambda g: ops.batched_moments(g, interpret=False, k_block=k_block),
        one_chip, (k, N))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k,k_block,pre", [
    (20, None, "identity"),
    (20, None, "sign"),
    (4096, None, "identity"),   # N-block shrunk to fit the (K, N-block) tile
    (1024, 128, "identity"),
    (12, 3, "sign"),
])
def test_ota_superpose(one_chip, k, k_block, pre):
    text = compiled_text(
        lambda g, s, z: ops.ota_superpose(g, s, z, 1.0, pre=pre,
                                          interpret=False, k_block=k_block),
        one_chip, (k, N), (k,), (N,))
    assert "tpu_custom_call" in text


def test_superpose_tile_budget_is_actionable():
    """A device axis too wide for even a 128-lane VMEM tile is refused up
    front, naming the fix, instead of failing inside the chip's compiler."""
    g = jnp.zeros((16_384, 256), jnp.float32)
    with pytest.raises(ValueError, match="k_block"):
        ops.ota_superpose(g, jnp.ones((16_384,)), jnp.zeros((256,)), 1.0,
                          interpret=True)
