"""Pallas kernel validation: shape/dtype sweeps against the jnp oracles in
ref.py (interpret mode on CPU), contraction properties, and integration of
the kernel-backed compressors into the inner loop."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional dev dep; skip cleanly when absent
from hypothesis import given, settings, strategies as st

from repro.core.compression import (
    BlockTopK,
    KernelBlockTopK,
    empirical_contraction,
)
from repro.kernels.ops import block_topk, quantize
from repro.kernels.pack_residuals import (
    pack_sparse_blocks,
    padded_k,
    unpack_sparse_blocks,
)
from repro.kernels.quantize import quantize_pallas
from repro.kernels.ref import (
    block_topk_ref,
    pack_ref,
    quantize_ref,
    unpack_ref,
)
from repro.kernels.topk_compress import block_topk_pallas

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("nb", [1, 3, 8, 17])
@pytest.mark.parametrize("block", [128, 256, 1024])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_kernel_matches_ref(nb, block, dtype):
    x = jax.random.normal(KEY, (nb, block), dtype)
    k = max(1, block // 8)
    got = block_topk_pallas(x, k=k, block=block, interpret=True)
    want = block_topk_ref(x, k)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=0
    )


@pytest.mark.parametrize("nb", [1, 5, 8])
@pytest.mark.parametrize("block", [128, 512])
@pytest.mark.parametrize("bits", [2, 4, 8])
def test_quant_kernel_matches_ref(nb, block, bits):
    x = jax.random.normal(KEY, (nb, block), jnp.float32)
    u = jax.random.uniform(jax.random.PRNGKey(1), (nb, block), jnp.float32)
    got, gs = quantize_pallas(x, u, bits=bits, block=block, interpret=True)
    want, ws = quantize_ref(x, u, bits)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(ws), atol=1e-7)


@pytest.mark.parametrize("nb", [1, 5, 8])
@pytest.mark.parametrize("bits", [2, 4, 8])
def test_quant_kernel_matches_ref_bf16(nb, bits):
    """bf16 blocks: both sides compute in float32 and round the outputs
    back to bf16 — identical bits."""
    block = 256
    x = jax.random.normal(KEY, (nb, block), jnp.bfloat16)
    u = jax.random.uniform(jax.random.PRNGKey(1), (nb, block), jnp.bfloat16)
    got, gs = quantize_pallas(x, u, bits=bits, block=block, interpret=True)
    want, ws = quantize_ref(x, u, bits)
    assert got.dtype == gs.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32)
    )
    np.testing.assert_array_equal(
        np.asarray(gs, np.float32), np.asarray(ws, np.float32)
    )


def _sparse_tiles(nb, block, k, dtype):
    x = jax.random.normal(KEY, (nb, block), dtype)
    return block_topk_ref(x, k)


@pytest.mark.parametrize("nb", [1, 8, 13])
@pytest.mark.parametrize("block", [128, 1024])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pack_unpack_kernels_match_ref(nb, block, dtype):
    k = max(1, block // 10)
    x = _sparse_tiles(nb, block, k, dtype)
    vals, idx = pack_sparse_blocks(x, k=k, block=block, interpret=True)
    wv, wi = pack_ref(x, padded_k(k))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(wv))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(wi))
    back = unpack_sparse_blocks(vals, idx, block=block, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(back), np.asarray(unpack_ref(wv, wi, block))
    )
    np.testing.assert_array_equal(
        np.asarray(back), np.asarray(x, np.float32)
    )


def test_pack_kernel_drops_survivors_past_kpad():
    """A row with more than kpad nonzeros keeps its first kpad (lane
    order), every slot valid — the oracle's contract."""
    block = 256
    x = jnp.arange(1, 2 * block + 1, dtype=jnp.float32).reshape(2, block)
    vals, idx = pack_sparse_blocks(x, k=128, block=block, interpret=True)
    wv, wi = pack_ref(x, 128)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(wv))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(wi))
    assert (np.asarray(idx) < block).all()


def test_topk_bisection_selects_k_per_block():
    """Bisection plus the lane-order tie-break keeps exactly k per block."""
    block, k = 256, 32
    x = jax.random.normal(KEY, (16, block))
    out = block_topk_pallas(x, k=k, block=block, interpret=True)
    kept = np.asarray(jnp.sum(out != 0, axis=-1))
    assert (kept == k).all(), kept


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_ties_keep_exactly_k_in_lane_order(dtype):
    """Magnitudes tied at the threshold (common in bf16 residuals) are kept
    in lane order up to k, so no block exceeds the packed record budget."""
    block, k = 512, 51
    sign = jnp.where(jnp.arange(block) % 3 == 0, -1.0, 1.0)
    row0 = sign * 0.5                      # every magnitude tied
    row1 = jnp.where(jnp.arange(block) < 20, 2.0, 0.5) * sign  # 20 above
    x = jnp.stack([row0, row1]).astype(dtype)
    out = block_topk_pallas(x, k=k, block=block, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out, np.float32),
        np.asarray(block_topk_ref(x, k), np.float32),
    )
    got = np.asarray(out, np.float32)
    assert ((got != 0).sum(axis=1) == k).all()
    np.testing.assert_array_equal(got[0, :k], np.asarray(x[0, :k], np.float32))
    assert (got[:, k:] == 0).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_kernel_equals_lax_top_k_selection(dtype):
    """The kernel compressor and the jnp BlockTopK (`jax.lax.top_k`) keep
    the same entries, ties included, on rows with wide dynamic range."""
    block, k = 256, 26
    scale = jnp.exp2(jnp.linspace(-30.0, 10.0, block))
    x = jax.random.normal(KEY, (4, block)) * scale
    x = jnp.concatenate([x, jnp.round(x * 4.0) / 4.0]).astype(dtype)
    got = KernelBlockTopK(ratio=k / block, block=block)(KEY, x)
    want = BlockTopK(ratio=k / block, block=block)(KEY, x)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32)
    )


def test_topk_bisection_close_to_exact_topk():
    """The kept set's energy is >= exact top-k energy minus tiny slack."""
    block, k = 512, 64
    x = jax.random.normal(KEY, (4, block))
    out = block_topk_pallas(x, k=k, block=block, interpret=True)
    exact_vals, _ = jax.lax.top_k(jnp.abs(x), k)
    exact_energy = np.asarray(jnp.sum(exact_vals**2, -1))
    got_energy = np.asarray(jnp.sum(out**2, -1))
    assert (got_energy >= exact_energy * 0.999).all()


@pytest.mark.parametrize("shape", [(100,), (3, 7, 11), (1025,), (4096,)])
def test_block_topk_wrapper_arbitrary_shapes(shape):
    x = jax.random.normal(KEY, shape)
    out = block_topk(x, ratio=0.25, block=128)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()
    # output is a masked version of input
    mask = np.asarray(out) != 0
    np.testing.assert_allclose(np.asarray(out)[mask], np.asarray(x)[mask])


def test_kernel_compressor_contractive():
    comp = KernelBlockTopK(ratio=0.25, block=128)
    for i in range(5):
        x = jax.random.normal(jax.random.PRNGKey(i), (777,))
        r = float(empirical_contraction(comp, KEY, x))
        assert r <= 1.0 - comp.delta + 1e-5


def test_quant_wrapper_roundtrip_error_bounded():
    x = jax.random.normal(KEY, (2048,))
    out = quantize(x, KEY, bits=8, block=256)
    step = 2.0 * float(jnp.max(jnp.abs(x))) / 255.0
    assert float(jnp.max(jnp.abs(out - x))) <= step + 1e-6


@settings(max_examples=20, deadline=None)
@given(
    st.integers(min_value=1, max_value=2000),
    st.sampled_from([128, 256]),
    st.integers(min_value=0, max_value=2**31 - 1),
)
def test_topk_kernel_property_matches_ref(d, block, seed):
    """Property sweep: wrapper == oracle for any flat length."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (d,))
    got = block_topk(x, ratio=0.2, block=block)
    nb = -(-d // block)
    padded = jnp.pad(x, (0, nb * block - d)).reshape(nb, block)
    k = max(1, round(0.2 * block))
    want = block_topk_ref(padded, k).reshape(-1)[:d]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0)


def test_kernel_compressor_in_inner_loop():
    """End-to-end: the kernel compressor drives Algorithm 2 to consensus."""
    from repro.core.inner_loop import inner_init, inner_loop
    from repro.core.topology import ring

    m, d = 4, 96
    rng = np.random.default_rng(0)
    A = jnp.asarray(
        np.stack([np.eye(d) * (1 + 0.1 * i) for i in range(m)]), jnp.float32
    )
    b = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    grad_fn = lambda w: jnp.einsum("mij,mj->mi", A, w - b)
    topo = ring(m)
    W = jnp.asarray(topo.W, jnp.float32)
    comp = KernelBlockTopK(ratio=0.25, block=128)
    st0 = inner_init(jnp.zeros((m, d)), grad_fn)
    stK, metrics = inner_loop(st0, KEY, grad_fn, W, comp, 0.4, 0.1, 200)
    assert float(metrics["consensus_err"]) < 1e-3
