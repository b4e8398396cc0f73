"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one JSON line last on stdout. Everything a cell, a
configuration, a traffic mix or a per-layer metric needs is a file found by
its name (see ``harness/discovery.py``); this file and ``harness/`` hold no
list of them.
"""
import os
import sys
import time

_T0 = time.perf_counter()  # set-up is counted from here (plus interpreter start-up)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness.main import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], t0=_T0))
