"""The per-scope reduction (`chipbench.scopes`): the map from a compiled
program's text to scopes, the exact partition of a trace's busy time on
hand-built traces and on one recorded on a TPU v5e, and the scopes of the
tiny-dense cell's round compiled on the CPU."""

import gzip
import json
import re
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from chipbench import scopes, trace_reduce
from chipbench.kernels import kernel_topk
from chipbench.tests.conftest import ROOT, tiny_model

DATA = Path(__file__).resolve().parent / "data"
KERNEL = {"kernel": r"^block_topk_pallas"}

HLO = """HloModule jit_round, entry_computation_layout={(f32[4]{0})->bf16[4]{0}}

%fused_computation (param_0: f32[4]) -> bf16[4] {
  %param_0 = f32[4]{0} parameter(0)
  %add.1 = f32[4]{0} add(%param_0, %param_0), metadata={op_name="jit(round)/update/add"}
  %mul.1 = f32[4]{0} multiply(%add.1, %param_0), metadata={op_name="jit(round)/exchange/mul"}
  ROOT %convert.1 = bf16[4]{0} convert(%add.1), metadata={op_name="jit(round)/while/body/closed_call"}
}

%fused_two (param_0.1: f32[4]) -> (f32[4], f32[4]) {
  %param_0.1 = f32[4]{0} parameter(0)
  %sub.2 = f32[4]{0} subtract(%param_0.1, %param_0.1), metadata={op_name="jit(round)/update/sub"}
  %mul.2 = f32[4]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(round)/exchange/mul"}
  ROOT %tuple.1 = (f32[4]{0}, f32[4]{0}) tuple(%sub.2, %mul.2)
}

%region_add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.2 = f32[] add(%a, %b), metadata={op_name="jit(round)/compress/add"}
}

%called (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %abs.1 = f32[4]{0} abs(%p), metadata={op_name="jit(round)/meter/abs"}
}

ENTRY %main (Arg_0: f32[4]) -> bf16[4] {
  %Arg_0 = f32[4]{0} parameter(0)
  %dot.3 = f32[4]{0} dot(%Arg_0, %Arg_0), metadata={op_name="jit(round)/ll_oracle/vmap(transpose(jvp()))/dot_general"}
  %negate.6 = f32[4]{0} negate(%Arg_0), metadata={op_name="jit(round)/vmap(transpose(jvp(ul_oracle)))/neg"}
  %sub.7 = f32[4]{0} subtract(%Arg_0, %Arg_0), metadata={op_name="jit(round)/compress/while/body/update/mul;compress/sub"}
  %copy.8 = f32[4]{0} copy(%Arg_0)
  %c = f32[] constant(0)
  %reduce.5 = f32[] reduce(%Arg_0, %c), dimensions={0}, to_apply=%region_add, metadata={op_name="jit(round)/while/body"}
  %call.9 = f32[4]{0} call(%Arg_0), to_apply=%called
  %fusion.10 = (f32[4]{0}, f32[4]{0}) fusion(%Arg_0), kind=kLoop, calls=%fused_two, metadata={op_name="jit(round)/exchange/mul"}
  ROOT %fusion.4 = bf16[4]{0} fusion(%Arg_0), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(round)/while/body/closed_call"}
}
"""


def test_op_scopes_rules():
    m = scopes.op_scopes(HLO, scopes.SCOPES)
    # applied computations (the fusion's, the reduction's) are left out;
    # a call's computation runs as ops of its own
    assert set(m) == {"Arg_0", "dot.3", "negate.6", "sub.7", "copy.8", "c",
                      "reduce.5", "call.9", "fusion.4", "fusion.10", "p", "abs.1"}
    assert m["dot.3"] == "ll_oracle"
    assert m["negate.6"] == "ul_oracle"  # a name wrapped by transforms counts
    assert m["sub.7"] == "compress"  # the innermost segment that names one
    assert m["copy.8"] == m["Arg_0"] == m["reduce.5"] == scopes.UNSCOPED
    assert m["abs.1"] == "meter"
    # a fusion counts under its own metadata; where that names no scope,
    # under its root, read through a convert naming none
    assert m["fusion.10"] == "exchange"
    assert m["fusion.4"] == "update"
    assert scopes.mixed_fusions(HLO, scopes.SCOPES) == {"fusion.4", "fusion.10"}
    assert scopes.module_name(HLO) == "jit_round"
    assert scopes.scopes_in(HLO, scopes.SCOPES) == set(scopes.SCOPES)


def ev(name, start, end):
    return NS(name=name, start_ns=float(start), end_ns=float(end), stats=[])


def hand_trace(ops, modules=(("jit_round(7)", 0, 2000),)):
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("window", 0, 1000), ev("enqueue", 0, 50), ev("wait", 50, 1000)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev(*m) for m in modules]),
        NS(name="XLA Ops", events=[ev(*o) for o in ops])])
    return NS(planes=[host, dev])


OPS = [
    ("%fusion.1 = f32[8] fusion(f32[8] %x)", 100, 300),
    ("%block_topk_pallas.2 = bf16[8] custom-call(bf16[8] %x)", 300, 400),
    ("%fusion.2 = bf16[8] fusion(bf16[8] %block_topk_pallas.2)", 350, 450),
    ("%fusion.3 = f32[8] fusion(f32[8] %y)", 600, 900),
    ("%fusion.4 = f32[8] fusion(f32[8] %y)", 1100, 1200),  # after the window
]
MAP = {"fusion.1": "ll_oracle", "fusion.2": "update", "fusion.3": "meter",
       "fusion.4": "meter", "while.7": scopes.UNSCOPED}


def test_labels_partition_busy_time_with_overlapping_ops():
    pd = hand_trace(OPS)
    got = scopes.scope_times(pd, MAP, KERNEL, "jit_round")
    busy = trace_reduce.reduce(pd, KERNEL)["busy_s"]
    # the latest-started op owns each instant: fusion.2 takes [350, 450]
    # from the kernel, which keeps [300, 350]
    assert {k: round(v * 1e9) for k, v in got.items()} == {
        "kernel": 50, "ll_oracle": 200, "meter": 300, "update": 100,
        "unscoped": 0, "unmapped": 0}
    assert sum(got.values()) == pytest.approx(busy, rel=1e-12)


def test_a_loop_gives_way_to_its_body():
    pd = hand_trace([("%while.7 = (s32[]) while((s32[]) %t)", 90, 460)] + OPS)
    got = scopes.scope_times(pd, MAP, KERNEL, "jit_round")
    busy = trace_reduce.reduce(pd, KERNEL)["busy_s"]
    # the loop keeps only [90, 100] and [450, 460], between its body's ops
    assert round(got["unscoped"] * 1e9) == 20
    assert round(got["ll_oracle"] * 1e9) == 200
    assert sum(got.values()) == pytest.approx(busy, rel=1e-12)


def test_a_scope_with_no_time_is_an_error():
    with pytest.raises(ValueError, match="exchange"):
        scopes.scope_times(hand_trace(OPS), dict(MAP, **{"fusion.9": "exchange"}), KERNEL,
                           "jit_round")
    with pytest.raises(ValueError, match="kernel"):
        scopes.scope_times(hand_trace(OPS), MAP, {"kernel": r"^topk_kernel"}, "jit_round")


def test_a_stale_map_is_an_error():
    stale = {k: v for k, v in MAP.items() if v != "meter"}
    with pytest.raises(ValueError, match="stale map"):
        scopes.scope_times(hand_trace(OPS), stale, KERNEL, "jit_round")
    # ops of another program (outside the module's events) are unmapped
    other = [("jit_round(7)", 0, 500), ("jit_fold_in(3)", 590, 910)]
    no_meter = dict(MAP, **{"fusion.3": "update", "fusion.4": "update"})
    with pytest.raises(ValueError, match="stale map"):
        scopes.scope_times(hand_trace(OPS, other), no_meter, KERNEL, "jit_round")
    got = scopes.scope_times(hand_trace(OPS, other), no_meter, KERNEL, "jit_round",
                             strict=False)
    assert round(got["unmapped"] * 1e9) == 300
    got = scopes.scope_times(hand_trace(OPS), no_meter, KERNEL, "jit_round")
    assert round(got["update"] * 1e9) == 400


def test_kernel_names_may_not_be_scope_labels():
    with pytest.raises(ValueError, match="clash"):
        scopes.scope_times(hand_trace(OPS), MAP, {"update": r"^block_topk_pallas"},
                           "jit_round")


def test_read_without_scopes_and_with_some():
    bare = re.sub(r', metadata=\{[^}]*\}', "", HLO)
    assert scopes.read(hand_trace(OPS), bare, KERNEL) is None
    with pytest.raises(ValueError, match="ul_oracle"):
        scopes.read(hand_trace(OPS), HLO.replace("ul_oracle", "ul_oracles"), KERNEL)


def _tiny_round_text() -> str:
    import jax

    from chipbench import harness

    tr = json.loads((ROOT / "chipbench" / "traffic" / "ring2.topk.json").read_text())
    tr["seq"] = 64
    b = harness.build(harness.Cell("tiny-dense.ring2.topk", 1, tiny_model("dense"), tr,
                                   {}, [], []), 2**31 + 977)
    st = jax.eval_shape(b.init_fn, b.wkey, b.df, b.dg)
    return b.round_fn.lower(st, b.rkey(1), b.df, b.dg).compile().as_text()


def test_tiny_round_carries_every_scope():
    text = _tiny_round_text()
    assert scopes.scopes_in(text, scopes.SCOPES) == set(scopes.SCOPES)
    m = scopes.op_scopes(text, scopes.SCOPES)
    # every instruction outside the applied computations is mapped
    applied = set(re.findall(r"\b(?:calls|to_apply|comparator)=%([^\s,)}]+)", text))
    want, comp = set(), None
    for line in text.splitlines():
        h = re.match(r"^(?:ENTRY )?%(\S+) \(", line)
        if h:
            comp = h.group(1)
        i = re.match(r"^\s+(?:ROOT )?%(\S+) = ", line)
        if i and comp not in applied:
            want.add(i.group(1))
    assert set(m) == want and len(want) > 1000
    assert set(m.values()) <= set(scopes.SCOPES) | {scopes.UNSCOPED}


def test_recorded_v5e_trace():
    """One round of the tiny-dense cell (seq 64) traced on a v5e with the
    scopes, and the round's compiled text."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_serialized_xspace(
        gzip.decompress((DATA / "v5e_scopes.xplane.pb.gz").read_bytes()))
    text = gzip.decompress((DATA / "v5e_scopes.hlo.txt.gz").read_bytes()).decode()
    r = scopes.read(pd, text, {"compress_kernel": kernel_topk.PATTERN})
    busy = trace_reduce.reduce(pd, {"compress": kernel_topk.PATTERN})["busy_s"]
    # by hand: each ns of the window painted with the latest-started op
    # running then (ops painted in order of start), summed per label, in us
    assert {k: round(v * 1e6, 3) for k, v in r["scope_s"].items()} == {
        "compress_kernel": 1418.733, "compress": 36.502, "exchange": 134.519,
        "ll_oracle": 195.404, "meter": 120.581, "ul_oracle": 217.519,
        "update": 0.039, "unscoped": 159.707, "unmapped": 0.0}
    assert round(busy * 1e6, 3) == 2283.004
    assert sum(r["scope_s"].values()) == pytest.approx(busy, rel=1e-12)
    assert round(r["mixed_s"] * 1e6, 3) == 227.135
