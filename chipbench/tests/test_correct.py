"""``correct`` at a tiny size on the CPU: a sound run passes; the control
(the reference in fp8 in the program's place) and each fault the cell can
have, planted under the timed path, fail.  The harness's look for a chip
is replaced by JAX's CPU devices; the rest of a run is driven as on the
chip, with a half-second window."""

import json
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness
from chipbench.tests.conftest import CONFIGS, ROOT, tiny_model

SEED = 2**31 + 977


def tiny_cell():
    tr = json.loads((ROOT / "chipbench" / "traffic" / "ring2.topk.json").read_text())
    tr["seq"] = 64
    limits = json.loads((CONFIGS / "tiny-dense.ring2.topk.json").read_text())["limits"]
    return harness.Cell("tiny-dense.ring2.topk", 1, tiny_model("dense"), tr,
                        limits, [{"name": "s_per_round", "unit": "s"}], [])


@pytest.fixture
def cpu_chips(monkeypatch):
    monkeypatch.setattr(harness, "find_chips", lambda n: (jax.devices(), None))


def run_tiny():
    return harness.run(tiny_cell(), SEED, 0.5, False, time.time())


def test_sound_run_is_correct(cpu_chips):
    r = run_tiny()
    assert r["correct"], r["check"]
    assert list(r)[-1] == "check"
    assert r["metrics"]["s_per_round"]["value"] > 0


def test_control_fp8_fails(monkeypatch, cpu_chips):
    real = harness.program_check

    def control(b):
        state, mets, _ = real(b)
        readings = harness.check.Readings(None)
        readings.as_dict = lambda: harness.reference_check(b, precision="fp8")
        return state, mets, readings

    monkeypatch.setattr(harness, "program_check", control)
    assert not run_tiny()["correct"]


def _unchanged(round_fn):
    def fn(state, key, problem, topo, cfg):
        _, met = round_fn(state, key, problem, topo, cfg)
        return jax.tree.map(lambda v: v + 0, state), met
    return fn


def _mix_left_out(round_fn):
    def fn(state, key, problem, topo, cfg):
        return round_fn(state, key, problem, topo, cfg, W=jnp.eye(topo.m))
    return fn


def _half_batch(make):
    def fn(mcfg, data_train, data_val, m, ridge):
        half = lambda d: {k: v[..., : v.shape[-1] // 2] for k, v in d.items()}
        return make(mcfg, half(data_train), half(data_val), m, ridge=ridge)
    return fn


@pytest.mark.parametrize("fault", ["state_unchanged", "mix_left_out", "half_batch"])
def test_fault_fails(monkeypatch, cpu_chips, fault):
    if fault == "half_batch":
        monkeypatch.setattr(harness, "make_lm_bilevel", _half_batch(harness.make_lm_bilevel))
    else:
        wrap = _unchanged if fault == "state_unchanged" else _mix_left_out
        monkeypatch.setattr(harness, "c2dfb_round", wrap(harness.c2dfb_round))
    assert not run_tiny()["correct"]


def test_no_chip_no_result():
    with pytest.raises(harness.NoChip):
        harness.run(tiny_cell(), SEED, 0.5, False, time.time())


def test_command_fails_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's
    files, the command exits non-zero and prints no result."""
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = subprocess.run(
        [sys.executable, "chipbench/run_cell.py", "--workload", "mamba2.ring2.topk",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip().startswith("{")
