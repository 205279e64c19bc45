"""Algorithm 1 end-to-end on an analytically solvable bilevel problem, plus
the paper's experimental tasks at test scale."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bilevel_problem import BilevelProblem
from repro.core.c2dfb import (
    C2DFBConfig,
    add_measured_bytes,
    c2dfb_round,
    outer_round_bytes,
    init_state,
    round_wire_bytes,
    run,
)
from repro.core.topology import erdos_renyi, ring, two_hop
from repro.core.types import broadcast_nodes, node_mean, tree_sq_norm
from repro.data.bilevel_tasks import coefficient_tuning_task

KEY = jax.random.PRNGKey(0)


def make_quadratic_bilevel(m=6, dx=5, dy=7, seed=0):
    """f_i = 0.5||y - A_i x||^2 + 0.5*mu_x||x||^2,  g_i = 0.5||y - B_i x||^2.

    Then y*(x) = B_bar x and
    psi(x) = (1/2m) sum_i ||(B_bar - A_i) x||^2 + 0.5 mu_x ||x||^2, which has
    a unique minimum at x = 0 with an analytic gradient.
    """
    rng = np.random.default_rng(seed)
    scale = 1.0 / np.sqrt(dx + dy)  # keep operator norms ~1 so L ~ O(1)
    A = jnp.asarray(scale * rng.normal(size=(m, dy, dx)), jnp.float32)
    B = jnp.asarray(scale * rng.normal(size=(m, dy, dx)), jnp.float32)
    mu_x = 0.1

    data_f = {"A": A}
    data_g = {"B": B}

    def f(x, y, d):
        return 0.5 * jnp.sum((y - d["A"] @ x) ** 2) + 0.5 * mu_x * jnp.sum(x**2)

    def g(x, y, d):
        return 0.5 * jnp.sum((y - d["B"] @ x) ** 2)

    problem = BilevelProblem(f=f, g=g, data_f=data_f, data_g=data_g, m=m)

    B_bar = np.asarray(B).mean(0)

    def true_hypergrad(x):
        x = np.asarray(x)
        acc = mu_x * x
        for i in range(m):
            Ai = np.asarray(A[i])
            r = (B_bar - Ai) @ x
            acc += (B_bar - Ai).T @ r / m
        return acc

    return problem, true_hypergrad, mu_x


def test_hypergrad_estimate_matches_analytic():
    """With exact inner solves (large K, no compression) the C2DFB tracker
    s_x averages to the analytic grad psi(x_bar)."""
    problem, true_hg, _ = make_quadratic_bilevel()
    m = problem.m
    topo = ring(m)
    cfg = C2DFBConfig(
        lam=100.0, eta_out=0.0, gamma_out=0.5, eta_in=0.5, gamma_in=0.5,
        K=400, compressor="identity",
    )
    x0 = broadcast_nodes(jnp.asarray(np.full(5, 0.7), jnp.float32), m)
    y0 = broadcast_nodes(jnp.zeros(7, jnp.float32), m)
    state = init_state(problem, cfg, x0, y0)
    state, _ = c2dfb_round(state, KEY, problem, topo, cfg)
    got = np.asarray(node_mean(state.u_prev))
    want = true_hg(np.full(5, 0.7, np.float32))
    # bias is O(kappa^3/lam); with lam=100 expect close agreement
    np.testing.assert_allclose(got, want, rtol=0.08, atol=0.02)


def test_lambda_controls_hypergrad_bias():
    """Lemma 1: ||grad psi_lam - grad psi|| = O(1/lam)."""
    problem, true_hg, _ = make_quadratic_bilevel()
    m = problem.m
    topo = ring(m)
    errs = []
    for lam in [5.0, 50.0, 500.0]:
        cfg = C2DFBConfig(
            lam=lam, eta_out=0.0, gamma_out=0.5, eta_in=0.5, gamma_in=0.5,
            K=800, compressor="identity",
        )
        x0 = broadcast_nodes(jnp.asarray(np.full(5, 0.7), jnp.float32), m)
        y0 = broadcast_nodes(jnp.zeros(7, jnp.float32), m)
        state = init_state(problem, cfg, x0, y0)
        state, _ = c2dfb_round(state, KEY, problem, topo, cfg)
        got = np.asarray(node_mean(state.u_prev))
        errs.append(np.linalg.norm(got - true_hg(np.full(5, 0.7, np.float32))))
    assert errs[2] < errs[1] < errs[0]


@pytest.mark.parametrize("topo_fn", [ring, two_hop, lambda m: erdos_renyi(m, 0.5, 1)])
def test_converges_to_stationary_point(topo_fn):
    """Full algorithm drives ||grad psi|| and consensus errors down."""
    problem, true_hg, _ = make_quadratic_bilevel()
    m = problem.m
    topo = topo_fn(m)
    cfg = C2DFBConfig(
        lam=50.0, eta_out=0.3, gamma_out=0.5, eta_in=0.5, gamma_in=0.5,
        K=30, compressor="topk", comp_ratio=0.5,
    )
    x0 = broadcast_nodes(jnp.asarray(np.full(5, 0.7), jnp.float32), m)
    y0 = broadcast_nodes(jnp.zeros(7, jnp.float32), m)
    state, metrics = run(problem, topo, cfg, x0, y0, T=60, key=KEY)
    hg = np.asarray(metrics["hypergrad_norm"])
    assert hg[-1] < 0.05 * hg[0]
    x_bar = np.asarray(node_mean(state.x))
    assert np.linalg.norm(true_hg(x_bar)) < 0.05
    assert float(metrics["x_consensus_err"][-1]) < 2e-3


def test_heterogeneous_initial_x():
    """Nodes starting at different x still reach consensus + stationarity."""
    problem, true_hg, _ = make_quadratic_bilevel()
    m = problem.m
    topo = ring(m)
    cfg = C2DFBConfig(
        lam=50.0, eta_out=0.3, gamma_out=0.5, eta_in=0.5, gamma_in=0.5,
        K=30, compressor="topk", comp_ratio=0.5,
    )
    x0 = jax.random.normal(KEY, (m, 5))
    y0 = broadcast_nodes(jnp.zeros(7, jnp.float32), m)
    state, metrics = run(problem, topo, cfg, x0, y0, T=60, key=KEY)
    assert float(metrics["x_consensus_err"][-1]) < 2e-3
    assert float(metrics["hypergrad_norm"][-1]) < 0.05


def test_wire_bytes_accounting():
    problem, _, _ = make_quadratic_bilevel()
    m = problem.m
    cfg = C2DFBConfig(K=10, compressor="topk", comp_ratio=0.2)
    topo = ring(m)
    x0 = broadcast_nodes(jnp.zeros(5, jnp.float32), m)
    y0 = broadcast_nodes(jnp.zeros(7, jnp.float32), m)
    state = init_state(problem, cfg, x0, y0)
    acc = round_wire_bytes(state, cfg, topo)
    # outer: 2 tensors * 5 floats * 4B * m ; inner: 2 loops * K * 2 msgs
    assert acc["outer_bytes"] == 2 * 5 * 4 * m
    k = max(1, round(0.2 * 7))
    assert acc["inner_bytes"] == 2 * (2 * k * 8 * 10 * m)
    assert acc["total_bytes"] == acc["outer_bytes"] + acc["inner_bytes"]


def test_measured_bytes_exact_past_int32():
    # an LM-width backbone: the dense outer term alone passes 2^31, and the
    # host sum stays an exact int64 next to the traced int32 inner count
    x = {
        "embed": jax.ShapeDtypeStruct((4, 32064, 3072), jnp.bfloat16),
        "w": jax.ShapeDtypeStruct((4, 3072, 8192), jnp.bfloat16),
    }
    outer = outer_round_bytes(x)
    assert outer == 2 * (32064 * 3072 + 3072 * 8192) * 4 * 4 > 2**31
    inner = jnp.asarray([2**31 - 1, 7], jnp.int32)
    mets = add_measured_bytes({"inner_bytes": inner, "h": 1.0}, x)
    assert "inner_bytes" not in mets and mets["h"] == 1.0
    assert mets["measured_bytes"].dtype == np.int64
    assert mets["measured_bytes"].tolist() == [2**31 - 1 + outer, 7 + outer]


def test_compressed_run_matches_uncompressed_quality():
    """Claim: reference-point compression does not degrade final quality."""
    problem, true_hg, _ = make_quadratic_bilevel()
    m = problem.m
    topo = ring(m)
    finals = {}
    for name, ratio in [("identity", 1.0), ("topk", 0.3)]:
        cfg = C2DFBConfig(
            lam=50.0, eta_out=0.3, gamma_out=0.5, eta_in=0.5, gamma_in=0.5,
            K=30, compressor=name, comp_ratio=ratio,
        )
        x0 = broadcast_nodes(jnp.asarray(np.full(5, 0.7), jnp.float32), m)
        y0 = broadcast_nodes(jnp.zeros(7, jnp.float32), m)
        _, metrics = run(problem, topo, cfg, x0, y0, T=60, key=KEY)
        finals[name] = float(metrics["hypergrad_norm"][-1])
    assert finals["topk"] < 2.5 * finals["identity"] + 1e-3


def test_coefficient_tuning_learns():
    """Paper §6.1 at test scale: accuracy improves well above chance."""
    bundle = coefficient_tuning_task(m=4, n=600, p=60, c=5, h=0.0, seed=0)
    topo = ring(4)
    cfg = C2DFBConfig(
        lam=10.0, eta_out=0.5, gamma_out=0.5, eta_in=0.3, gamma_in=0.5,
        K=10, compressor="topk", comp_ratio=0.2,
    )
    state, metrics = run(bundle.problem, topo, cfg, bundle.x0, bundle.y0, T=30, key=KEY)
    x_bar = node_mean(state.x)
    y_bar = node_mean(state.inner_y.d)
    acc = bundle.test_accuracy(x_bar, y_bar, bundle.predict_fn)
    assert acc > 0.5  # 5 classes, chance = 0.2
