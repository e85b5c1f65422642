"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers a kernel at real widths (bf16, B=2) for a
``v5e:2x2`` topology that is described, not attached, and checks that the
TPU compiler accepts it and that the Mosaic kernel is in the program
(``tpu_custom_call``). Interpret-mode tests cannot catch what this catches:
block shapes the TPU tiling refuses, VMEM overuse, unsupported relayouts.

The topology is described inside a module fixture, never at import, so
every pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import mask as mk
from repro.kernels import ops
from repro.kernels.paged import paged_attn_pallas

# (n_heads, n_kv_heads, head_dim): smollm-360m and qwen3-8b attention widths
WIDTHS = {"hd64": (15, 5, 64), "hd128": (32, 8, 128)}
B, T = 2, 2048
MASKS = {
    "causal": (mk.causal(), False),
    "document": (mk.document(), True),                 # dynamic segment IDs
    "document_static": (mk.document(boundaries=mk.doc_boundaries(T, 3)),
                        False),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no topology"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(lowered):
    hlo = lowered.compile().as_text()
    assert "tpu_custom_call" in hlo


def _qkv(sharding, width):
    hq, hkv, d = WIDTHS[width]
    return (_shape(sharding, (B, T, hq, d)), _shape(sharding, (B, T, hkv, d)),
            _shape(sharding, (B, T, hkv, d)))


def _segments(sharding, dynamic):
    if not dynamic:
        return {}
    seg = _shape(sharding, (B, T), jnp.int32)
    return {"q_segments": seg, "kv_segments": seg}


@pytest.mark.parametrize("mask_name", sorted(MASKS))
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_flash_fwd_compiles(one_chip, width, mask_name):
    mask, dynamic = MASKS[mask_name]
    q, k, v = _qkv(one_chip, width)
    _assert_kernel(ops.flash_fwd.lower(q, k, v, mask=mask,
                                       **_segments(one_chip, dynamic)))


@pytest.mark.parametrize("mask_name", sorted(MASKS))
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_flash_bwd_compiles(one_chip, width, mask_name):
    mask, dynamic = MASKS[mask_name]
    q, k, v = _qkv(one_chip, width)
    lse = _shape(one_chip, (B, T, q.shape[2]), jnp.float32)
    _assert_kernel(ops.flash_bwd.lower(q, k, v, q, lse, q, mask=mask,
                                       **_segments(one_chip, dynamic)))


@pytest.mark.parametrize("tq", [1, 4])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_paged_decode_compiles(one_chip, width, tq):
    hq, hkv, d = WIDTHS[width]
    n_blocks, bs, nb = 64, 16, 32
    q = _shape(one_chip, (B, tq, hq, d))
    pool = _shape(one_chip, (n_blocks, bs, hkv, d))
    table = _shape(one_chip, (B, nb), jnp.int32)
    lengths = _shape(one_chip, (B,), jnp.int32)
    fn = jax.jit(lambda *a: paged_attn_pallas(*a, mask=mk.causal()))
    _assert_kernel(fn.lower(q, pool, pool, table, lengths))
