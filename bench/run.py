"""Run one cell of the chip benchmark:

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The last line of standard output is the result as one JSON object; the
numbers that decide ``correct`` are also the last lines of standard
error, each beside its limit. Without a TPU (or with fewer chips than the
cell asks for) it exits non-zero and prints no result.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main())
