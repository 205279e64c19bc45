"""Named scopes on the layers of a C2DFB round (`repro.obs.scopes`): each
scope reaches the compiled round's ``op_name`` metadata, the oracle scopes
count their oracle sites, the scopes leave the compiled instructions as
they are, and host spans land in a profiler trace."""

import contextlib
import glob
import re

import jax
import jax.numpy as jnp
import pytest
from test_c2dfb import make_quadratic_bilevel

from repro.async_gossip.mixing import mix_delta_delayed
from repro.core.c2dfb import C2DFBConfig, c2dfb_round, init_state
from repro.core.gossip import mix_delta_dense, mix_step_dense
from repro.core.inner_loop import inner_init, inner_loop
from repro.core.topology import ring
from repro.core.types import broadcast_nodes
from repro.obs import Obs, oracle_trace_counts
from repro.obs.scopes import ORACLE_SCOPES, SCOPES, scope
from repro.obs.timeline import HostSpans

KEY = jax.random.PRNGKey(0)
CFG = C2DFBConfig(lam=10.0, eta_out=0.1, gamma_out=0.5, eta_in=0.1,
                  gamma_in=0.5, K=2, compressor="topk", comp_ratio=0.5)


def _round_args():
    problem, _, _ = make_quadratic_bilevel(m=4)
    topo = ring(problem.m)
    x0 = broadcast_nodes(jnp.full(5, 0.7, jnp.float32), problem.m)
    y0 = broadcast_nodes(jnp.zeros(7, jnp.float32), problem.m)
    state = init_state(problem, CFG, x0, y0)
    return problem, topo, state


def _compiled_round() -> str:
    problem, topo, state = _round_args()
    fn = jax.jit(lambda st, k: c2dfb_round(st, k, problem, topo, CFG))
    return fn.lower(state, KEY).compile().as_text()


def _op_names(text: str) -> list:
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.fixture(scope="module")
def round_text():
    return _compiled_round()


@pytest.mark.parametrize("name", SCOPES)
def test_every_scope_reaches_the_compiled_round(round_text, name):
    segs = {s for n in _op_names(round_text) for s in n.split("/")}
    assert name in segs


def test_unknown_scope_is_an_error():
    with pytest.raises(ValueError, match="unknown scope"):
        scope("mixing")


@pytest.mark.parametrize("name", SCOPES)
def test_oracle_scopes_count_their_sites(name):
    before = oracle_trace_counts()
    with scope(name, 3):
        pass
    after = oracle_trace_counts()
    delta = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    want = {ORACLE_SCOPES[name]: 3} if name in ORACLE_SCOPES else {}
    assert {k: v for k, v in delta.items() if v} == want


@pytest.mark.parametrize("fn", ["mix_delta_dense", "mix_step_dense", "mix_delta_delayed"])
def test_mixing_is_scoped_as_exchange(fn):
    W = jnp.asarray(ring(4).W, jnp.float32)
    x = {"a": jnp.ones((4, 3)), "b": jnp.ones((4, 2, 2))}
    if fn == "mix_delta_delayed":
        hist = jax.tree.map(lambda v: jnp.stack([v, 2 * v]), x)
        ages = jnp.zeros((4, 4), jnp.int32)
        lowered = jax.jit(mix_delta_delayed).lower(W, hist, ages)
    else:
        mix = {"mix_delta_dense": mix_delta_dense,
               "mix_step_dense": lambda W, x: mix_step_dense(W, 0.5, x)}[fn]
        lowered = jax.jit(mix).lower(W, x)
    text = lowered.compile().as_text()
    assert any("exchange" in n.split("/") for n in _op_names(text))


def test_inner_loop_metrics_are_the_read_ones():
    """The inner loop's metrics are those a round reads; the tracker's
    consensus error, which nothing read, is gone."""
    problem, _, state = _round_args()
    W = jnp.asarray(ring(problem.m).W, jnp.float32)
    grad_g = problem.grad_y_g()
    gz = lambda d: grad_g(d, state.x)
    st = inner_init(state.inner_z.d, gz)
    _, met = inner_loop(st, KEY, gz, W, CFG.make_compressor(), 0.5, 0.1, 2)
    assert set(met) == {"consensus_err", "compress_err", "msg_bytes"}


def _strip(text: str) -> list:
    """Opcode and shape of every instruction, metadata and numbering aside."""
    out = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) ([a-z][a-z0-9\-]*)\(", line)
        if m:
            out.append((m.group(2), m.group(1)))
    return out


def test_scopes_leave_the_instructions_as_they_are(round_text, monkeypatch):
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = _compiled_round()
    assert not any(s in n.split("/") for n in _op_names(bare) for s in SCOPES)
    assert _strip(bare) == _strip(round_text)


@pytest.mark.parametrize("kind", ["Obs", "HostSpans"])
def test_host_spans_land_in_a_profiler_trace(tmp_path, kind):
    from jax.profiler import ProfileData

    holder = Obs() if kind == "Obs" else HostSpans()
    f = jax.jit(lambda v: jnp.sin(v) * 2)
    v = jnp.ones(8)
    f(v).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with holder.span("x_span"):
            f(v).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    spans = holder.hostspans.spans if kind == "Obs" else holder.spans
    assert [s.name for s in spans] == ["x_span"]
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True))[-1]
    pd = ProfileData.from_file(path)
    names = [e.name for p in pd.planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events]
    assert "x_span" in names

