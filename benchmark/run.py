"""The benchmark's command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process does everything (a parent that had touched JAX would hold
the chip). The clock starts at the first line so that ``setup_s`` counts
the imports too. See ``harness.py``.
"""
import time

T_START = time.perf_counter()

import os   # noqa: E402
import sys  # noqa: E402

if __name__ == '__main__':
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark import harness
    sys.exit(harness.main(sys.argv[1:], T_START))
