"""Chip benchmark of C2DFB: one cell (model configuration x traffic) per run.

Run from the root of a checkout:

    python chipbench/run_cell.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

`BENCHMARK.json` names the cells; each configuration, traffic mix, cell
limit file and metric reader is a file of its own under this directory,
found by name.
"""
