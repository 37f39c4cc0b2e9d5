"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Loads the cell's configuration and traffic by name, fills the filter from
the seed, warms up the cell's own shapes, measures for ``--seconds``,
checks every verdict of the run against the plain reference, and prints
one JSON line last on standard output. Exits non-zero with no result when
JAX finds no accelerator, an accelerator missing from the peak table, or
fewer chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))
