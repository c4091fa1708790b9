#!/usr/bin/env python3
"""Chip benchmark of the federated SFVI engine.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with a TPU. The cell names
a configuration and a traffic mix in BENCHMARK.json; both are data files
under perfbench/. The last line of standard output is one JSON object
with ``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``
(and ``breakdown`` with ``--trace 1``); the numbers compared for
``correct`` end standard error and the result line. Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""
import sys
import time

T0 = time.perf_counter()  # set-up starts here, before JAX is imported

if __name__ == "__main__":
    import pathlib

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    from pbench.harness import main

    sys.exit(main(sys.argv[1:], T0))
