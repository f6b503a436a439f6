"""Record the small chip trace that ``tests/bench/test_bench_trace.py``
reads: the traced rounds of a cell cut to a test's size (8x8 images,
8 clients, 2 a round, batch 8), run through the harness on the chip:

    python3 bench/record_trace.py <out.xplane.pb>

Writes the trace and, beside it, ``<out.xplane.pb>.ctx.json``: what the
per-layer readers were given apart from the trace.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from bench import harness  # noqa: E402

CELL = "resnet8_r32_int8.lda100_k10"
SEED = 2 ** 33 + 21


def small_cell() -> dict:
    cell = harness.load_cell(CELL)
    cell["config_spec"] = dict(cell["config_spec"], image=[8, 8, 3])
    cell["traffic_spec"] = dict(cell["traffic_spec"], n_images=400,
                                n_clients=8, clients_per_round=2,
                                batch_size=8)
    return cell


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace.py needs a TPU", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    out = harness.run_cell(small_cell(), SEED, 0.0, True,
                           keep_trace=sys.argv[1])
    print(out["correct"], sorted(out["metrics"]), out["device"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
