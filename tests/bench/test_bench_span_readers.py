"""The per-layer metrics that read the server's round spans: seconds per
traced round in one span, and the round's time that none of its five
phases names."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

# reader -> the span it sums
SPAN_READERS = {"engine.stage_batches_s": "fl/stage_batches",
                "engine.h2d_s": "fl/h2d",
                "trainer.wait_s": "fl/train_wait",
                "engine.slice_s": "fl/slice",
                "codec.encode_s": "fl/encode",
                "engine.aggregate_s": "fl/aggregate"}


def _reader(name):
    return harness._module(ROOT / "bench" / "metrics" / f"{name}.py")


def _ctx(spans, rounds=2):
    return {"spans": spans, "rounds": rounds}


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_span_reader_sums_its_span_per_round(name):
    span = SPAN_READERS[name]
    spans = {span: [0.25, 0.5, 0.75, 0.5], "fl/other": [9.0]}
    assert _reader(name).read(_ctx(spans)) == pytest.approx(1.0)


@pytest.mark.parametrize("name", sorted(SPAN_READERS) + [
    "engine.unspanned_s"])
def test_span_reader_reads_nothing_without_its_span(name):
    spans = {"fl/other": [1.0]}
    assert _reader(name).read(_ctx(spans)) is None


def test_unspanned_is_the_round_less_its_five_phases():
    spans = {"fl/round": [2.0, 3.0],
             "fl/broadcast": [0.5, 0.5], "fl/client_train": [1.0, 1.5],
             "fl/pack": [0.25, 0.25], "fl/uplink": [0.125, 0.125],
             "fl/aggregate": [0.0625, 0.0625],
             # nested spans are inside the phases: not taken off again
             "fl/h2d": [0.25, 0.25], "fl/train_wait": [1.0, 1.0],
             "fl/slice": [0.1] * 20, "fl/encode": [0.1] * 20}
    got = _reader("engine.unspanned_s").read(_ctx(spans))
    assert got == pytest.approx((5.0 - 4.375) / 2)


def test_unspanned_without_a_phase_counts_it_as_unspanned():
    spans = {"fl/round": [1.0], "fl/client_train": [0.75]}
    got = _reader("engine.unspanned_s").read(_ctx(spans, rounds=1))
    assert got == pytest.approx(0.25)


def test_harness_reads_the_span_readers_by_file_name():
    """Listed for a cell, the seven readers are found by name and all
    read a traced round's spans, strict as on the chip."""
    cell = harness.load_cell(harness._json(ROOT / "BENCHMARK.json")
                             ["workloads"][0]["name"])
    names = [*SPAN_READERS, "engine.unspanned_s"]
    cell["per_layer"] = [{"name": n, "unit": "s/round"} for n in names]
    spans = {s: [0.5] for s in [*SPAN_READERS.values(), "fl/round",
                                "fl/broadcast", "fl/client_train",
                                "fl/pack", "fl/uplink"]}
    spans["fl/round"] = [3.0]
    got = harness.read_metrics(cell, _ctx(spans, rounds=1), strict=True)
    assert sorted(got) == sorted(names)
    assert got["engine.unspanned_s"]["value"] == pytest.approx(0.5)
