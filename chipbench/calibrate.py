#!/usr/bin/env python3
"""Readings that set a cell's check limits, on the chip at the cell's size.

    python chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 1,2,3] [--out file.jsonl]

For each seed: the program's check steps against the plain reference
(the lower readings).  For each control seed also: the reference in fp8
put in the program's place (the control), and two faults read the same
way: the gossip mix left out (W = I) and half of each node's tokens left
out (the mean over the rest).  Every comparison prints one JSON line, with the raw readings.
The benchmark's own runs do not run this.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _ints(s):
    return [int(v) for v in s.split(",") if v]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--out")
    args = ap.parse_args()

    import jax
    import numpy as np

    from chipbench import check, harness

    if jax.devices()[0].platform != "tpu":
        print("error: calibration readings are taken on the chip", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    cell = harness.load_cell(args.workload)
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        for k in ("readings", "reference"):
            if k in rec:  # the survivor masks are arrays: their gap is in "gaps"
                rec[k] = {n: v for n, v in rec[k].items() if n != "survivors"}
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for seed in args.seeds:
        b = harness.build(cell, seed)
        t0 = time.perf_counter()
        state, mets, prog = harness.program_check(b)
        jax.block_until_ready(state)
        del state, mets
        t1 = time.perf_counter()
        ref = harness.reference_check(b)
        t2 = time.perf_counter()
        emit({"workload": cell.name, "seed": seed, "run": "program",
              "gaps": check.compare(prog.as_dict(), ref),
              "program_s": t1 - t0, "reference_s": t2 - t1,
              "readings": prog.as_dict(), "reference": ref})
        if seed not in args.control_seeds:
            continue
        half = lambda d: {k: v[..., : v.shape[-1] // 2] for k, v in d.items()}
        runs = {
            "control_fp8": dict(precision="fp8"),
            "fault_no_mix": dict(W=np.eye(b.m)),
            "fault_half_batch": dict(data_f=half(b.data_f), data_g=half(b.data_g)),
        }
        for name, kw in runs.items():
            t3 = time.perf_counter()
            got = harness.reference_check(b, **kw)
            emit({"workload": cell.name, "seed": seed, "run": name,
                  "gaps": check.compare(got, ref),
                  "seconds": time.perf_counter() - t3, "readings": got})
    print(f"total {time.time() - T_START!r} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
