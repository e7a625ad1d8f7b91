"""``python -m benchmarks.chip ...``: the same as ``benchmarks/chip/run.py``."""
import sys

from benchmarks.chip.harness import main

sys.exit(main())
