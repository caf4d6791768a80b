"""Run one benchmark cell once on the accelerator and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cells, configurations, traffic mixes and metrics are named in
`BENCHMARK.json` at the root of the checkout; see `bench/harness.py`.
The last line of standard output is the result as one JSON object; a run
that finds no TPU, or fewer chips than the cell asks for, prints no result
and exits non-zero.
"""

import time

STARTED = time.monotonic()

import sys                      # noqa: E402
from pathlib import Path        # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the checkout's root, not this directory, heads the import path: the
# benchmark's modules are imported as `bench.*` and must not shadow any
# top-level module
sys.path[0] = str(ROOT)

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], ROOT, STARTED))
