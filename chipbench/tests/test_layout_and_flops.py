"""The benchmark's weight layout matches the program's parameter tree, and
the required-FLOP count matches a count by hand."""

import jax
import pytest

from chipbench import flops, weights
from chipbench.harness import model_config
from chipbench.tests.conftest import tiny_model
from repro.core.lm_bilevel import split_params
from repro.models.transformer import abstract_lm_params


@pytest.mark.parametrize("arch", ["dense", "ssm"])
def test_weight_layout_matches_program(arch):
    mc = tiny_model(arch)
    want, _ = abstract_lm_params(model_config(mc))
    got = jax.eval_shape(lambda k: weights.make_params(mc, k), jax.random.PRNGKey(0))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
    gx, gy = weights.split(got)
    wx, wy = split_params(want)
    assert jax.tree.structure(gx) == jax.tree.structure(wx)
    assert jax.tree.structure(gy) == jax.tree.structure(wy)


def test_weights_repeat_from_seed():
    mc = tiny_model("dense")
    a = weights.make_params(mc, weights.seed_key(2**31 + 5))
    b = weights.make_params(mc, weights.seed_key(2**31 + 5))
    c = weights.make_params(mc, weights.seed_key(5))
    assert all((x == y).all() for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not (a["lm_head"] == c["lm_head"]).all()


TRAFFIC = {"nodes": 2, "batch": 1, "seq": 64, "K": 3}


def test_dense_round_flops_by_hand():
    # tiny dense: d 256, 4 heads x 64, d_ff 512, vocab 512, 2 layers, 64 tokens
    mc = tiny_model("dense")
    T = 64
    layer = 2 * (4 * 256 * 256 + 3 * 256 * 512) * T + 4 * 4 * 64 * 64 * T
    # 2*(262144 + 393216)*64 = 83886080 in the matrices, 4194304 in attention
    assert layer == 88080384
    f_bb = 2 * layer
    f_head = 2 * 256 * 512 * T
    want = 2 * (8 * f_bb + (6 * 3 + 12) * f_head)
    assert flops.round_flops(mc, TRAFFIC) == want == 2 * (8 * 176160768 + 30 * 16777216)


def test_ssm_round_flops_by_hand():
    # tiny ssm: d 256, 8 heads x 64 (d_inner 512), state 32, 1 group, chunk 32
    mc = tiny_model("ssm")
    T, Q = 64, 32
    proj = 256 * (2 * 512 + 2 * 32 + 8) + 512 * 256  # 411648
    per_chunk = 2 * 8 * Q * (Q * 32 + Q * 64 + 2 * 64 * 32)  # 512 * 7168
    layer = 2 * proj * T + per_chunk * (T // Q)
    assert (proj, per_chunk) == (411648, 3670016)
    f_bb = 2 * layer
    f_head = 2 * 256 * 512 * T
    assert flops.round_flops(mc, TRAFFIC) == 2 * (8 * f_bb + 30 * f_head)


def test_mamba2_cell_flops_by_hand():
    # mamba2-2.7b.d1: d 2560, 80 heads x 64 (d_inner 5120), state 128, 1
    # group, chunk 256, 1 layer, vocab 50280, 1024 tokens
    import json
    from pathlib import Path

    mc = json.loads((Path(flops.__file__).parents[1] / "configs"
                     / "mamba2-2.7b.d1.json").read_text())["model"]
    proj = 2560 * (2 * 5120 + 2 * 128 + 80) + 5120 * 2560
    per_chunk = 2 * 80 * 256 * (256 * 128 + 256 * 64 + 2 * 64 * 128)
    assert (proj, per_chunk) == (40181760, 2684354560)
    f_bb = 2 * proj * 1024 + per_chunk * 4
    f_head = 2 * 2560 * 50280 * 1024
    assert (f_bb, f_head) == (93029662720, 263612006400)
    per_node = 8 * f_bb + 30 * f_head  # 8.653 TFLOP
    assert flops.round_flops(mc, dict(TRAFFIC, seq=1024)) == 2 * per_node
