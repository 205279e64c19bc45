"""Tests of the benchmark itself, on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests
"""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CONFIGS = Path(__file__).resolve().parent / "configs"


def tiny_model(arch: str) -> dict:
    return json.loads((CONFIGS / f"tiny-{arch}.json").read_text())["model"]


@pytest.fixture
def tiny():
    return tiny_model
