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
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import mask as mk
from repro.kernels import ops
from repro.kernels.block_sparse import kv_profile, q_profile
from repro.kernels.flash_attention import flash_tiles
from repro.kernels.paged import paged_attn_pallas

sys.path.append(str(Path(__file__).resolve().parents[1] / "bench"))
import scopes  # noqa: E402  (the benchmark's reader of kernel names)

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
    return hlo


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


@pytest.mark.parametrize("prune", [True, False])
@pytest.mark.parametrize("mask_name", sorted(MASKS))
def test_flash_calls_carry_their_names_and_grid_work(one_chip, mask_name,
                                                     prune):
    """Each flash call is named for the trace and carries, as
    ``kernel_metadata``, the tiles it ran with (the chooser's, at default
    tiles), its static grid work at those tiles, B·H times its
    ``GridProfile`` counts: the steps it launches and those whose compute
    body runs, and its body: bf16 MXU operands, and hd64's 1/8 scale
    folded into an operand tile."""
    mask, dynamic = MASKS[mask_name]
    q, k, v = _qkv(one_chip, "hd64")
    segs = _segments(one_chip, dynamic)

    def fwd_bwd(q, k, v, do, **kw):
        o, lse = ops.flash_fwd(q, k, v, mask=mask, prune=prune, **kw)
        return ops.flash_bwd(q, k, v, o, lse, do, mask=mask, prune=prune,
                             **kw)

    hlo = _assert_kernel(jax.jit(fwd_bwd).lower(q, k, v, q, **segs))
    got = {scopes.kernel(n): meta
           for n, (_, meta) in scopes.instructions(hlo).items()
           if scopes.kernel(n)}
    ops_of = {"flash_fwd": ("fwd", kv_profile),
              "flash_bwd.dq": ("dq", kv_profile),
              "flash_bwd.dkv": ("dkv", q_profile)}
    d = WIDTHS["hd64"][2]
    bh = B * q.shape[2]
    assert set(got) == set(ops_of)
    for name, (op, profile) in ops_of.items():
        br, bc = got[name]["block_q"], got[name]["block_kv"]
        assert (br, bc) == flash_tiles(T, T, d, d, mask, op), name
        p = profile(nq=T // br, nk=T // bc, br=br, bc=bc, mask=mask)
        if prune and mask.prunable:   # the body runs where the mask reaches
            want = {"launched": p.launched_steps,
                    "executed": p.executed_steps}
        else:                         # the body runs on every grid step
            want = {"launched": p.full_steps, "executed": p.full_steps}
        want = {k: bh * v for k, v in want.items()}
        assert got[name] == {**want, "block_q": br, "block_kv": bc,
                             "operand_dtype": "bfloat16",
                             "scale_folded": True}, name


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_flash_default_tiles_compile_at_training_length(one_chip, width):
    """fwd + bwd at the chooser's tiles, causal, batch 1 at seq 8192 (the
    single-chip training cell's shape; qwen3-8b's widths at its
    sequence-parallel chunk): VMEM overuse or a refused block shape at the
    tiles a TPU call runs with would fail here. Every kernel runs 1024 ×
    1024 tiles with bf16 MXU operands; the scale folds into an operand
    tile at hd64 (1/8) and not at hd128 (1/sqrt(128))."""
    hq, hkv, d = WIDTHS[width]
    q = _shape(one_chip, (1, 8192, hq, d))
    kv = _shape(one_chip, (1, 8192, hkv, d))

    def fwd_bwd(q, k, v, do):
        o, lse = ops.flash_fwd(q, k, v, mask=mk.causal())
        return ops.flash_bwd(q, k, v, o, lse, do, mask=mk.causal())

    hlo = _assert_kernel(jax.jit(fwd_bwd).lower(q, kv, kv, q))
    got = {scopes.kernel(n): meta
           for n, (_, meta) in scopes.instructions(hlo).items()
           if scopes.kernel(n)}
    assert set(got) == {"flash_fwd", "flash_bwd.dq", "flash_bwd.dkv"}
    for name, meta in got.items():
        assert (meta["block_q"], meta["block_kv"]) == (1024, 1024), name
        assert meta["operand_dtype"] == "bfloat16", name
        assert meta["scale_folded"] is (width == "hd64"), name


def test_paged_kernel_carries_its_name(one_chip):
    hq, hkv, d = WIDTHS["hd64"]
    q = _shape(one_chip, (B, 1, hq, d))
    pool = _shape(one_chip, (64, 16, hkv, d))
    table = _shape(one_chip, (B, 32), jnp.int32)
    lengths = _shape(one_chip, (B,), jnp.int32)
    fn = jax.jit(lambda *a: paged_attn_pallas(*a, mask=mk.causal()))
    hlo = _assert_kernel(fn.lower(q, pool, pool, table, lengths))
    assert any(n.startswith("paged_attn.")
               for n in scopes.instructions(hlo))
