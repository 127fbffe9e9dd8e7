"""One run of one cell of the benchmark:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result, one JSON object.
"""
import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmarks.lib.harness import main

    sys.exit(main(sys.argv[1:], T_START))
