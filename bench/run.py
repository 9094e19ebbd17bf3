"""Run one cell of the chip benchmark; see ``bench/harness.py``.

    python bench/run.py --workload version-p001.mixed-open --seed 1 --seconds 10 --trace 0
"""

import time

T_START = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
