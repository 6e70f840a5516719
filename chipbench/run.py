"""Run one cell of the chip benchmark once, from the checkout's root.

    python chipbench/run.py --workload rabbitct-512.scan --seed 7 \
        --seconds 30 --trace 0

The cell's pieces are found by name from ``BENCHMARK.json`` (see
``chipbench/README.md``).  Without a TPU as JAX's first device, with
fewer chips than the cell asks for, or without the program beside the
benchmark, the run exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness.runner import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))
