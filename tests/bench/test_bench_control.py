"""What decides ``correct``, shown to fail: a whole run of the harness on
the CPU at a size a test run holds (8x8 images, 8 clients, 2 a round,
batch 8), past the look for a chip.

- A sound run passes every exact check.
- The control, the reference computed in bfloat16 in the program's
  place, reads outside the cell's limits.
- Each fault this cell can have, planted under the timed path, makes the
  run incorrect: a round that returns the global tree unchanged, half of
  the cohort left out of the mean, an uplink altered where it is made.
  (The exchange between chips does not exist on one chip.)
"""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from bench.record_trace import CELL, SEED  # noqa: E402
from bench.record_trace import small_cell as _tiny_cell  # noqa: E402


@pytest.fixture(scope="module")
def sound():
    return harness.run_cell(_tiny_cell(), SEED, 0.0, False, control=True)


def test_sound_run_passes_the_exact_checks(sound):
    checks = sound["checks"]
    for name in ("uplink_bytes_gap", "feed_faults", "window_compiles"):
        assert checks[name]["value"] == 0 == checks[name]["limit"]
    assert checks["uplink_level_gap"]["value"] <= 1
    assert sound["attempted"] >= 1 and sound["failed"] == 0
    assert set(sound["metrics"]) == {"round_s", "uplink_bytes", "setup_s"}
    assert list(sound)[-1] == "checks"


def test_control_in_bfloat16_is_not_correct(sound):
    assert sound["control"]["correct"] is False, sound["control"]
    limits = harness.load_cell(CELL)["limits"]
    assert any(sound["control"][n] > limits[n] for n in harness.TRAINING)


@pytest.mark.parametrize("fault", harness.FAULTS)
def test_planted_fault_is_not_correct(fault):
    out = harness.run_cell(_tiny_cell(), SEED, 0.0, False, faults=(fault,))
    assert out["correct"] is False
    failed = [n for n, c in out["checks"].items() if c["value"] > c["limit"]]
    assert failed


def test_traced_run_reads_every_per_layer_metric(monkeypatch):
    """The traced window's plumbing on the CPU, with the trace reduction
    (which needs a device plane) replaced by a fixed one."""
    reduced = {"window_s": 2.0, "busy_s": 1.5, "idle_share": 0.25,
               "programs": {"jit_run": 1.0},
               "ops": {"quant_pack_kernel": 0.01, "dequant_agg_kernel": 0.02,
                       "fusion.1": 1.0},
               "op_counts": {"quant_pack_kernel": 12,
                             "dequant_agg_kernel": 4, "fusion.1": 9},
               "host": {}, "idle_gaps": [("fl/pack", 0.1)], "devices": 1}

    def window(server, rounds, keep=None):
        for _ in range(rounds):
            harness._round(server)
        return 2.0, reduced

    monkeypatch.setattr(harness, "_traced_window", window)
    v5e = harness.peaks("TPU v5 lite")
    monkeypatch.setattr(harness, "peaks", lambda kind: v5e)
    out = harness.run_cell(_tiny_cell(), SEED, 0.0, True)
    spec = harness.load_cell(CELL)
    assert set(out["metrics"]) == {m["name"] for m in spec["per_layer"]}
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["device.idle_share"] == 25.0
    assert 0 < m["trainer.useful_step_share"] <= 100
    assert m["engine.stage_s"] > 0 and m["engine.pack_s"] > 0
    assert out["device"]["busy_s"] == 1.5
    assert out["device"]["window_s"] == 2.0
    assert out["breakdown"]["idle_gaps"] == [["fl/pack", 0.1]]
    assert out["attempted"] == spec["traffic_spec"]["trace_rounds"]
