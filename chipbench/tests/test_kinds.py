"""The kind modules (`chipbench.kinds`): they keep the parent's weight
layout and FLOP counts to the bit, a kind module that a configuration
brings is found by its kind map alone, and each reference layer agrees
with the program's layer at a tiny size in float32."""

import importlib
import json
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops, harness, kinds, weights
from chipbench.reference.c2dfb import Reference
from chipbench.reference.matmul import Matmul
from chipbench.reference.norm import rms_norm
from chipbench.tests.conftest import ROOT, tiny_model


def mamba2_model() -> dict:
    return json.loads((ROOT / "chipbench" / "configs" / "mamba2-2.7b.d1.json")
                      .read_text())["model"]


MODELS = {"tiny-dense": lambda: tiny_model("dense"), "tiny-ssm": lambda: tiny_model("ssm"),
          "mamba2-2.7b.d1": mamba2_model}

# (path, shape, dtype, init, scale) of every leaf in flattening order (the
# order fixes each leaf's fold_in index), as the layout before the kind
# modules made them
PARENT_LEAVES = {
    "tiny-dense": [
        ("['blocks'][0]['attn']['wk']", (2, 256, 256), 'bfloat16', 'normal', 0.0625),
        ("['blocks'][0]['attn']['wo']", (2, 256, 256), 'bfloat16', 'normal', 0.0625),
        ("['blocks'][0]['attn']['wq']", (2, 256, 256), 'bfloat16', 'normal', 0.0625),
        ("['blocks'][0]['attn']['wv']", (2, 256, 256), 'bfloat16', 'normal', 0.0625),
        ("['blocks'][0]['mlp']['wg']", (2, 256, 512), 'bfloat16', 'normal', 0.0625),
        ("['blocks'][0]['mlp']['wi']", (2, 256, 512), 'bfloat16', 'normal', 0.0625),
        ("['blocks'][0]['mlp']['wo']", (2, 512, 256), 'bfloat16', 'normal', 0.044194173824159216),
        ("['blocks'][0]['norm1']", (2, 256), 'bfloat16', 'ones', 1.0),
        ("['blocks'][0]['norm2']", (2, 256), 'bfloat16', 'ones', 1.0),
        ("['embed']", (512, 256), 'bfloat16', 'normal', 0.02),
        ("['final_norm']", (256,), 'bfloat16', 'ones', 1.0),
        ("['lm_head']", (256, 512), 'bfloat16', 'normal', 0.0625),
    ],
    "tiny-ssm": [
        ("['blocks'][0]['mamba']['a_log']", (2, 8), 'float32', 'log_uniform_1_16', 1.0),
        ("['blocks'][0]['mamba']['conv_w']", (2, 4, 576), 'bfloat16', 'normal', 0.5),
        ("['blocks'][0]['mamba']['d_skip']", (2, 8), 'float32', 'ones', 1.0),
        ("['blocks'][0]['mamba']['dt_bias']", (2, 8), 'float32', 'zeros', 1.0),
        ("['blocks'][0]['mamba']['norm']", (2, 512), 'bfloat16', 'ones', 1.0),
        ("['blocks'][0]['mamba']['w_in']", (2, 256, 1096), 'bfloat16', 'normal', 0.0625),
        ("['blocks'][0]['mamba']['w_out']", (2, 512, 256), 'bfloat16', 'normal', 0.044194173824159216),
        ("['blocks'][0]['norm1']", (2, 256), 'bfloat16', 'ones', 1.0),
        ("['embed']", (512, 256), 'bfloat16', 'normal', 0.02),
        ("['final_norm']", (256,), 'bfloat16', 'ones', 1.0),
        ("['lm_head']", (256, 512), 'bfloat16', 'normal', 0.0625),
    ],
    "mamba2-2.7b.d1": [
        ("['blocks'][0]['mamba']['a_log']", (1, 80), 'float32', 'log_uniform_1_16', 1.0),
        ("['blocks'][0]['mamba']['conv_w']", (1, 4, 5376), 'bfloat16', 'normal', 0.5),
        ("['blocks'][0]['mamba']['d_skip']", (1, 80), 'float32', 'ones', 1.0),
        ("['blocks'][0]['mamba']['dt_bias']", (1, 80), 'float32', 'zeros', 1.0),
        ("['blocks'][0]['mamba']['norm']", (1, 5120), 'bfloat16', 'ones', 1.0),
        ("['blocks'][0]['mamba']['w_in']", (1, 2560, 10576), 'bfloat16', 'normal', 0.01976423537605237),
        ("['blocks'][0]['mamba']['w_out']", (1, 5120, 2560), 'bfloat16', 'normal', 0.013975424859373685),
        ("['blocks'][0]['norm1']", (1, 2560), 'bfloat16', 'ones', 1.0),
        ("['embed']", (50280, 2560), 'bfloat16', 'normal', 0.02),
        ("['final_norm']", (2560,), 'bfloat16', 'ones', 1.0),
        ("['lm_head']", (2560, 50280), 'bfloat16', 'normal', 0.01976423537605237),
    ],
}

# flops.round_flops at 2 nodes, K 3, one sequence of 64 and of 1024
# tokens, as counted before the kind modules
PARENT_FLOPS = {
    "tiny-dense": (3825205248.0, 93415538688.0),
    "tiny-ssm": (2927624192.0, 46841987072.0),
    "mamba2-2.7b.d1": (1075534888960.0, 17305194987520.0),
}


@pytest.mark.parametrize("name", sorted(MODELS))
def test_kind_modules_keep_the_parent_leaves(name):
    paths, _ = jax.tree_util.tree_flatten_with_path(
        weights.leaf_specs(MODELS[name]()), is_leaf=weights.is_leaf)
    got = [(jax.tree_util.keystr(p), leaf.shape, leaf.dtype, leaf.init, leaf.scale)
           for p, leaf in paths]
    assert got == PARENT_LEAVES[name]


@pytest.mark.parametrize("name", sorted(MODELS))
def test_kind_modules_keep_the_parent_flops(name):
    traffic = {"nodes": 2, "batch": 1, "K": 3}
    got = tuple(flops.round_flops(MODELS[name](), dict(traffic, seq=s)) for s in (64, 1024))
    assert got == PARENT_FLOPS[name]


TOY = '''
"""A toy kind: x + (x W) * gate."""

from chipbench.weights import Leaf, dense_leaf


def leaves(mc, R, p):
    d, dt = mc["d_model"], mc["dtype"]
    return {"gate": Leaf((R, d), dt, "ones"), "w": dense_leaf(d, d, dt, R)}


def layer(blk, mc, x, mm):
    return x + mm("bsd,de->bse", x, blk["w"]) * blk["gate"]


def forward_flops(mc, seq, tokens, p):
    return 2.0 * mc["d_model"] ** 2 * tokens
'''


@pytest.fixture
def toy_kind(tmp_path, monkeypatch):
    """A kind module that exists only in this test, in a directory of its
    own on the kind package's search path."""
    where = tmp_path / "more_kinds"
    where.mkdir()
    (where / "toy_gate.py").write_text(textwrap.dedent(TOY))
    monkeypatch.setattr(kinds, "__path__", [*kinds.__path__, str(where)])
    importlib.invalidate_caches()
    yield "toy_gate"
    sys.modules.pop("chipbench.kinds.toy_gate", None)


def _toy_cell(tmp_path) -> harness.Cell:
    """A checkout whose one configuration maps a kind of its own."""
    model = dict(tiny_model("ssm"), pattern=["mamba", "toy"], num_layers=2)
    conf = {"name": "toy-hybrid", "model": model,
            "kinds": {"mamba": "mamba", "toy": "toy_gate"}}
    (tmp_path / "toy-hybrid.json").write_text(json.dumps(conf))
    bench = {"configs": [{"name": "toy-hybrid", "file": "toy-hybrid.json"}],
             "workloads": [{"name": "mamba2.ring2.topk", "config": "toy-hybrid",
                            "traffic": "ring2.topk", "chips": 1}],
             "end_to_end": [], "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return harness.load_cell("mamba2.ring2.topk", root=tmp_path)


def test_a_kind_module_added_at_test_time(tmp_path, toy_kind):
    cell = _toy_cell(tmp_path)
    mc = cell.model
    assert mc[kinds.KEY] == {"mamba": "mamba", "toy": "toy_gate"}
    harness.model_config(dict(mc, pattern=["mamba"]))  # the map stays out of the program's

    # weights: the toy block's leaves at period position 1
    specs = weights.leaf_specs(mc)
    assert specs["blocks"][1] == {"gate": weights.Leaf((1, 256), "bfloat16", "ones"),
                                  "w": weights.dense_leaf(256, 256, "bfloat16", 1)}
    assert "mamba" in specs["blocks"][0]

    # FLOPs: the toy layer's forward beside the mamba layer's (1 repeat)
    seq, T = 64, 64
    f_mamba = 2.0 * (256 * (2 * 512 + 2 * 32 + 8) + 512 * 256) * T + 3670016 * (T // 32)
    f_toy = 2.0 * 256 * 256 * T
    f_head = 2.0 * 256 * 512 * T
    traffic = {"nodes": 2, "batch": 1, "seq": seq, "K": 3}
    assert flops.round_flops(mc, traffic) == 2 * (8 * (f_mamba + f_toy) + 30 * f_head)

    # reference: the backbone runs the mamba layer, then the toy layer
    params = jax.jit(lambda k: weights.make_params(mc, k))(weights.seed_key(2**31 + 3))
    x, _ = weights.split(params)
    tokens = jnp.arange(seq, dtype=jnp.int32)[None] % mc["vocab_size"]
    data = {"tokens": np.zeros((1, 1, seq), np.int32), "labels": np.zeros((1, 1, seq), np.int32)}
    ref = Reference(mc, {"nodes": 1, "lam": 1.0, "ridge": 0.0}, data, data, np.eye(1))
    got = ref._hidden(x, tokens)
    f32 = jax.tree.map(lambda v: v.astype(jnp.float32)[0], x["blocks"])
    mm = Matmul("f32")
    h = kinds.modules(dict(mc, pattern=["mamba"]))[0].layer(
        f32[0], mc, x["embed"].astype(jnp.float32)[tokens], mm)
    want = h + mm("bsd,de->bse", h, f32[1]["w"]) * f32[1]["gate"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_an_unmapped_kind_is_an_error():
    with pytest.raises(KeyError, match="toy"):
        weights.leaf_specs(dict(tiny_model("ssm"), pattern=["mamba", "toy"]))


# f32 tolerance of the repository's tiny tests of a layer against its
# plain form (tests/test_ssm_moe.py: the chunked SSD against the naive)
RTOL, ATOL = 1e-3, 2e-3

VARIANTS = {
    "gqa": lambda: dict(tiny_model("dense"), num_kv_heads=2),
    "no_rope": lambda: dict(tiny_model("dense"), use_rope=False),
    "mamba_mlp": lambda: dict(tiny_model("ssm"), d_ff=512),
}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_reference_layers_match_the_program(variant):
    """Grouped-query attention, attention without positions, and a Mamba
    layer with an MLP: the reference backbone (weights from the seed, in
    float32) against the program's `forward_hidden` on the same weights."""
    from repro.models.transformer import forward_hidden

    mc = dict(VARIANTS[variant](), dtype="float32")
    seq = 64
    params = jax.jit(lambda k: weights.make_params(mc, k))(weights.seed_key(2**31 + 11))
    tokens = jax.random.randint(jax.random.PRNGKey(7), (1, seq), 0, mc["vocab_size"])
    data = {"tokens": np.zeros((1, 1, seq), np.int32), "labels": np.zeros((1, 1, seq), np.int32)}
    ref = Reference(mc, {"nodes": 1, "lam": 1.0, "ridge": 0.0}, data, data, np.eye(1))
    x, y = weights.split(params)
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(lambda p, t: forward_hidden(p, harness.model_config(mc), t))(
            params, tokens)
        got = rms_norm(ref._hidden(x, tokens), y["final_norm"], mc["norm_eps"])
    assert float(jnp.std(want)) > 0.5  # the layers did not collapse the states
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=RTOL, atol=ATOL)
