"""Entry point of the benchmark: runs one cell once (see harness.py).

    python3 benchmarks/chip/run.py --workload fuego9.selfplay --seed 7 \
        --seconds 10 --trace 0
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # libtpu logs to /tmp else
ROOT = Path(__file__).resolve().parents[2]
sys.path[0] = str(ROOT)          # import the package, not its files

from benchmarks.chip.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START, root=ROOT))
