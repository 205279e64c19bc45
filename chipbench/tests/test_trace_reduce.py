"""The trace reduction on a hand-built trace and on one recorded on a
TPU v5e (two jitted calls, each a two-step scan over block top-k,
quantize and a matmul)."""

import gzip
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from chipbench import trace_reduce

DATA = Path(__file__).resolve().parent / "data"


def ev(name, start, end, **stats):
    return NS(name=name, start_ns=float(start), end_ns=float(end),
              stats=list(stats.items()))


def hand_trace():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("window", 0, 1000), ev("enqueue", 0, 50), ev("wait", 50, 1000)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_step", 100, 900)]),
        NS(name="XLA Ops", events=[
            ev("fusion.1", 100, 300),
            ev("%block_topk_pallas.2 = bf16[8] custom-call(bf16[8] %x)", 300, 400),
            ev("%fusion.2 = bf16[8] fusion(bf16[8] %block_topk_pallas.2)", 350, 450),
            ev("fusion.3", 600, 900),
            ev("fusion.4", 1100, 1200),  # after the window: left out
        ])])
    return NS(planes=[host, dev])


def test_busy_idle_kernel_and_gaps_by_hand():
    r = trace_reduce.reduce(hand_trace(), {"topk": r"^block_topk_pallas"})
    assert r["device_planes"] == 1
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(650e-9)  # [100, 450] and [600, 900]
    assert r["kernel_s"] == {"topk": pytest.approx(100e-9)}
    # gaps [450, 600] and [900, 1000] while waiting, [0, 100] while enqueuing
    assert [[n, round(s * 1e9)] for n, s in r["idle_gaps"]] == [
        ["wait", 150], ["enqueue", 100], ["wait", 100]]
    assert [n for n, _ in r["device_ops"]][:1] == ["fusion.3"]


def test_loop_ops_give_way_to_their_body():
    t = hand_trace()
    ops = t.planes[1].lines[1].events
    ops.insert(0, ev("%while.7 = (s32[]) while(...)", 90, 460))
    r = trace_reduce.reduce(t, {"topk": r"^block_topk_pallas"})
    assert r["busy_s"] == pytest.approx(670e-9)  # [90, 460] and [600, 900]
    assert "while.7" not in [n for n, _ in r["device_ops"]]
    assert r["kernel_s"]["topk"] == pytest.approx(100e-9)


def test_a_kernel_pattern_that_matches_nothing_is_an_error():
    with pytest.raises(ValueError, match="topk"):
        trace_reduce.reduce(hand_trace(), {"topk": r"^topk_kernel"})


def test_no_device_ops_is_an_error():
    t = hand_trace()
    t.planes = t.planes[:1]
    with pytest.raises(ValueError):
        trace_reduce.reduce(t, {})


def test_recorded_v5e_trace():
    path = DATA / "v5e_kernels.xplane.pb.gz"
    if not path.exists():
        pytest.skip("no recorded trace")
    from jax.profiler import ProfileData

    pd = ProfileData.from_serialized_xspace(gzip.decompress(path.read_bytes()))
    from chipbench.kernels import kernel_topk

    # the recorded trace also holds the Pallas stochastic quantizer's calls
    r = trace_reduce.reduce(pd, {"topk": kernel_topk.PATTERN,
                                 "quant": r"^quantize_pallas(\.\d+)?$"})
    assert r["device_planes"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    # by hand: each call is 4 custom calls per kernel (2 scan steps x 2
    # calls); top-k 27.79 us and quantize 1.443 us each
    assert r["kernel_s"]["topk"] == pytest.approx(4 * 27.79e-6, rel=1e-3)
    assert r["kernel_s"]["quant"] == pytest.approx(4 * 1.443e-6, rel=1e-3)
    assert r["kernel_s"]["topk"] + r["kernel_s"]["quant"] < r["busy_s"]
    assert len(r["idle_gaps"]) >= 1
