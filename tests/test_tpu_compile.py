"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Compile-only: the topology is described, not attached, so nothing runs.
The TPU compiler refuses what interpret mode accepts (unaligned slices,
too much VMEM), so these guard the kernels for the chip at no chip time.
Widths are granite-moe-1b-a400m's (Hq 16, Hkv 8, head_dim 64, d_model
1024) plus head_dim 128.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape_on(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return spec


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_topology_is_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"


def test_rmsnorm_compiles(shape_on):
    text = _compiled_text(
        lambda x, w: rmsnorm(x, w), shape_on((4096, 1024)), shape_on((1024,))
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("head_dim", [64, 128])
def test_flash_attention_compiles(shape_on, head_dim):
    b, hq, hkv, length = 4, 16, 8, 2048
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        shape_on((b, hq, length, head_dim)),
        shape_on((b, hkv, length, head_dim)),
        shape_on((b, hkv, length, head_dim)),
    )
    assert "tpu_custom_call" in text


def test_decode_attention_compiles(shape_on):
    b, hq, hkv, cache, head_dim = 4, 16, 8, 4096, 64
    text = _compiled_text(
        lambda q, k, v, n: decode_attention(q, k, v, n),
        shape_on((b, hq, head_dim)),
        shape_on((b, hkv, cache, head_dim)),
        shape_on((b, hkv, cache, head_dim)),
        shape_on((), jnp.int32),
    )
    assert "tpu_custom_call" in text
