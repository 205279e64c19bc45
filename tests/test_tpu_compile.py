"""The main-path Pallas kernels compile for a described TPU v5e.

Interpret mode (every other kernel test) cannot see Mosaic's rules: block
shapes that break the (8, 128) tiling, primitives it does not lower
(cumsum, bf16 compares on v5e), kernels that overflow VMEM.  These tests
compile each kernel at the phi3-mini LM-head width (3072 x 32064 =
96,192 blocks of 1024), and block top-k also at the mamba2 head (2560 x
50280 = 125,700 blocks) on two nodes, for a ``v5e:2x2`` topology that
is described, not attached, and check that the compiled program holds
the Mosaic kernel (``tpu_custom_call``).  Nothing runs; results are
checked against the jnp oracles in tests/test_kernels.py (interpret
mode) and on the chip by chip_smoke.py.

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU compiler library, and every test
worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.pack_residuals import (
    pack_sparse_blocks,
    padded_k,
    unpack_sparse_blocks,
)
from repro.kernels.quantize import quantize_pallas
from repro.kernels.topk_compress import block_topk_pallas

PHI3_HEAD_BLOCKS = 3072 * 32064 // 1024  # 96,192
MAMBA2_HEAD_BLOCKS = 2560 * 50280 // 1024  # 125,700
BLOCK = 1024
RATIO = 0.1


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _k(block: int) -> int:
    return max(1, round(RATIO * block))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_topk_compiles_for_v5e(one_chip, dtype):
    x = jax.ShapeDtypeStruct((PHI3_HEAD_BLOCKS, BLOCK), dtype, sharding=one_chip)
    text = _compile_text(
        lambda a: block_topk_pallas(a, k=_k(BLOCK), block=BLOCK, interpret=False),
        x,
    )
    assert "tpu_custom_call" in text


def test_block_topk_compiles_for_v5e_at_mamba2_head(one_chip):
    # vmapped over 2 nodes, as `compress_stacked` calls it: a tile height
    # that overflows VMEM fails here, not on the chip
    x = jax.ShapeDtypeStruct(
        (2, MAMBA2_HEAD_BLOCKS, BLOCK), jnp.bfloat16, sharding=one_chip
    )
    text = _compile_text(
        jax.vmap(lambda a: block_topk_pallas(
            a, k=_k(BLOCK), block=BLOCK, interpret=False)),
        x,
    )
    assert "tpu_custom_call" in text
    assert " pad(" not in text  # a partial last tile, no padding copy


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_compiles_for_v5e(one_chip, dtype):
    x = jax.ShapeDtypeStruct((PHI3_HEAD_BLOCKS, BLOCK), dtype, sharding=one_chip)
    text = _compile_text(
        lambda a, u: quantize_pallas(a, u, bits=4, block=BLOCK, interpret=False),
        x, x,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("block", [1024, 4096])
def test_pack_compiles_for_v5e(one_chip, block):
    nb = PHI3_HEAD_BLOCKS * 1024 // block
    x = jax.ShapeDtypeStruct((nb, block), jnp.float32, sharding=one_chip)
    kpad = padded_k(_k(block))
    text = _compile_text(
        lambda a: pack_sparse_blocks(a, k=kpad, block=block, interpret=False),
        x,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("block", [1024, 4096])
def test_unpack_compiles_for_v5e(one_chip, block):
    nb = PHI3_HEAD_BLOCKS * 1024 // block
    kpad = padded_k(_k(block))
    vals = jax.ShapeDtypeStruct((nb, kpad), jnp.float32, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((nb, kpad), jnp.int32, sharding=one_chip)
    text = _compile_text(
        lambda v, i: unpack_sparse_blocks(v, i, block=block, interpret=False),
        vals, idx,
    )
    assert "tpu_custom_call" in text
