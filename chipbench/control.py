"""The control's readings on the chip, for setting a cell's limit.

    python chipbench/control.py --workload rabbitct-256.scan \
        --seconds 30 --seeds 11 12 13

For each seed this runs the cell as ``run.py`` does, and then puts the
control in the program's place: the answers the window produced are
computed again by the float32 reference with its projective transform
at ``Precision.HIGH`` (``harness/reference.py``), over the same views at
the same voxels, and the run's check compares those.  It prints each
run's result line, which has to read ``"correct": false``; the
program's own reading of the same answers is on standard error.  The
seeds run one after another in this one process.  The benchmark's own
runs never run it.
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness.runner import execute  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    for seed in args.seeds:
        result = execute(Path.cwd(), args.workload, seed, args.seconds,
                         False, t_start=time.perf_counter(), control=True)
        result["seed"] = seed
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
