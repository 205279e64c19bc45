#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python chipbench/run_cell.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Needs the TPU chips the cell asks for; without them it exits 2 and
prints no result.  With ``--trace 0`` the result carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics (read from a
profiler trace of a few rounds).  The check numbers and their limits end
standard error and the result line.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from chipbench import harness

    cell = harness.load_cell(args.workload)
    harness.enable_compile_cache()
    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
