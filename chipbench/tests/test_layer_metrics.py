"""The per-layer metrics that read the program's named scopes: the
harness gathers the scopes and kernels their readers declare (``SCOPE``,
``KERNEL``), hands them to the trace reductions, and each reader turns
its label's seconds into ms a round."""

import gzip
import importlib
import sys
from pathlib import Path
from types import ModuleType
from types import SimpleNamespace as NS

import pytest

from chipbench import harness, scopes
from chipbench.kernels import kernel_topk
from chipbench.tests.test_scopes import ev

DATA = Path(__file__).resolve().parent / "data"
SCOPE_METRICS = {
    "ll_oracle_ms_per_round": "ll_oracle", "ul_oracle_ms_per_round": "ul_oracle",
    "exchange_ms_per_round": "exchange", "compress_xla_ms_per_round": "compress",
    "meter_ms_per_round": "meter", "update_ms_per_round": "update",
    "unscoped_ms_per_round": "unscoped",
}


def cell_per_layer() -> list:
    return harness.load_cell("mamba2.ring2.topk").per_layer


def test_the_cell_lists_the_scope_metrics():
    names = [m["name"] for m in cell_per_layer()]
    assert set(SCOPE_METRICS) <= set(names)
    assert harness.declared(cell_per_layer()) == (list(SCOPE_METRICS.values()), [])


def test_recorded_v5e_trace_gives_the_partition():
    """One round of the tiny-dense cell traced on a v5e (the recording of
    `test_scopes.test_recorded_v5e_trace`), read as the harness reads a
    traced run of the cell."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_serialized_xspace(
        gzip.decompress((DATA / "v5e_scopes.xplane.pb.gz").read_bytes()))
    text = gzip.decompress((DATA / "v5e_scopes.hlo.txt.gz").read_bytes()).decode()
    reduced, part = harness.read_trace(pd, text, "kernel_topk", cell_per_layer())
    want = scopes.read(pd, text, {"kernel_topk": kernel_topk.PATTERN})
    assert part == want
    ctx = NS(scope_s=part["scope_s"], rounds=1, trace=reduced)
    got = {name: importlib.import_module(f"chipbench.metrics.{name}").read(ctx)
           for name in SCOPE_METRICS}
    assert got == {name: 1e3 * want["scope_s"][label] for name, label in SCOPE_METRICS.items()}
    # by hand (us): as frozen in test_scopes
    assert {n: round(v * 1e3, 3) for n, v in got.items()} == {
        "ll_oracle_ms_per_round": 195.404, "ul_oracle_ms_per_round": 217.519,
        "exchange_ms_per_round": 134.519, "compress_xla_ms_per_round": 36.502,
        "meter_ms_per_round": 120.581, "update_ms_per_round": 0.039,
        "unscoped_ms_per_round": 159.707}
    # with the kernel's own reader they sum to the busy time
    kernel = importlib.import_module("chipbench.metrics.compress_kernel_ms_per_round").read(ctx)
    assert sum(got.values()) + kernel == pytest.approx(1e3 * reduced["busy_s"], abs=1e-9)


def test_without_scopes_the_readers_give_nothing():
    ctx = NS(scope_s=None, rounds=3)
    for name in SCOPE_METRICS:
        assert importlib.import_module(f"chipbench.metrics.{name}").read(ctx) is None


# A round of a later configuration: the six scopes, a scope of its own
# ("moe") and a kernel of its own beside the compressor's.
HLO = """HloModule jit_round, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

ENTRY %main (Arg_0: f32[4]) -> f32[4] {
  %Arg_0 = f32[4]{0} parameter(0)
  %ll.1 = f32[4]{0} negate(%Arg_0), metadata={op_name="jit(round)/ll_oracle/neg"}
  %ul.2 = f32[4]{0} negate(%Arg_0), metadata={op_name="jit(round)/ul_oracle/neg"}
  %ex.3 = f32[4]{0} negate(%Arg_0), metadata={op_name="jit(round)/exchange/neg"}
  %co.4 = f32[4]{0} negate(%Arg_0), metadata={op_name="jit(round)/compress/neg"}
  %me.5 = f32[4]{0} negate(%Arg_0), metadata={op_name="jit(round)/meter/neg"}
  %up.6 = f32[4]{0} negate(%Arg_0), metadata={op_name="jit(round)/update/neg"}
  %moe.7 = f32[4]{0} negate(%Arg_0), metadata={op_name="jit(round)/moe/neg"}
  %copy.8 = f32[4]{0} copy(%Arg_0)
  %block_topk_pallas.9 = f32[4]{0} custom-call(%Arg_0), custom_call_target="tpu_custom_call", metadata={op_name="jit(round)/compress/pallas_call"}
  ROOT %toy_gmm.10 = f32[4]{0} custom-call(%Arg_0), custom_call_target="tpu_custom_call", metadata={op_name="jit(round)/moe/pallas_call"}
}
"""
OPS = ["ll.1", "ul.2", "ex.3", "co.4", "me.5", "up.6", "moe.7", "copy.8"]
KERNELS = ["block_topk_pallas.9", "toy_gmm.10"]


def hand_trace():
    ops = [ev(f"%{n} = f32[4] negate(f32[4] %Arg_0)", 100 * i, 100 * i + 100)
           for i, n in enumerate(OPS)]
    ops += [ev(f"%{n} = f32[4] custom-call(f32[4] %Arg_0)", 800 + 100 * i, 900 + 100 * i)
            for i, n in enumerate(KERNELS)]
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[ev("window", 0, 1000)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_round(1)", 0, 1000)]),
        NS(name="XLA Ops", events=ops)])
    return NS(planes=[host, dev])


def _module(monkeypatch, name, **attrs):
    mod = ModuleType(name)
    mod.__dict__.update(attrs)
    monkeypatch.setitem(sys.modules, name, mod)


def test_a_declared_scope_and_kernel_reach_ctx(monkeypatch):
    """Metric readers, a kernel module and a scope that exist only in this
    test: the harness finds them by the metrics' names alone."""
    _module(monkeypatch, "chipbench.kernels.toy_gmm", PATTERN=r"^toy_gmm(\.\d+)?$")
    _module(monkeypatch, "chipbench.metrics.moe_ms_per_round", SCOPE="moe")
    _module(monkeypatch, "chipbench.metrics.toy_gmm_ms_per_round", KERNEL="toy_gmm")
    per_layer = [{"name": "moe_ms_per_round"}, {"name": "toy_gmm_ms_per_round"},
                 {"name": "unscoped_ms_per_round"}]
    assert harness.declared(per_layer) == (["moe", "unscoped"], ["toy_gmm"])
    reduced, part = harness.read_trace(hand_trace(), HLO, "kernel_topk", per_layer)
    assert {k: round(v * 1e9) for k, v in reduced["kernel_s"].items()} == {
        "compress": 100, "toy_gmm": 100}
    assert {k: round(v * 1e9) for k, v in part["scope_s"].items()} == {
        "kernel_topk": 100, "toy_gmm": 100, "compress": 100, "exchange": 100,
        "ll_oracle": 100, "meter": 100, "moe": 100, "ul_oracle": 100, "update": 100,
        "unscoped": 100, "unmapped": 0}
    # a scope that the program does not carry stops the reading
    with pytest.raises(ValueError, match="moe"):
        harness.read_trace(hand_trace(), HLO.replace("/moe/", "/experts/"), "kernel_topk",
                           per_layer)


def test_a_kernel_declared_twice_counts_under_both_names(monkeypatch):
    """A reader that declares the compressor's own kernel: the reduction
    counts the calls under ``compress`` and under the module's name, and
    the partition gives them to the module's name once."""
    _module(monkeypatch, "chipbench.metrics.topk_ms_per_round", KERNEL="kernel_topk")
    _module(monkeypatch, "chipbench.metrics.moe_ms_per_round", SCOPE="moe")
    per_layer = [{"name": "topk_ms_per_round"}, {"name": "moe_ms_per_round"}]
    reduced, part = harness.read_trace(hand_trace(), HLO, "kernel_topk", per_layer)
    assert {k: round(v * 1e9) for k, v in reduced["kernel_s"].items()} == {
        "compress": 100, "kernel_topk": 100}
    # the toy kernel is declared by no reader here: its time stays with its scope
    assert round(part["scope_s"]["kernel_topk"] * 1e9) == 100
    assert round(part["scope_s"]["moe"] * 1e9) == 200
