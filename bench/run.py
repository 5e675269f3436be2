#!/usr/bin/env python3
"""Run one cell of the benchmark (``BENCHMARK.json``) on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the compared numbers on standard error and one JSON result line
last on standard output; exits non-zero, with no result, when JAX finds no
TPU or fewer chips than the cell asks for, or the run cannot finish.
"""
import sys
import time

T_START = time.perf_counter()

if __name__ == "__main__":
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench.harness import main
    sys.exit(main(t_start=T_START))
