"""The benchmark harness on the CPU: cells, traffic mixes and metrics are
found as files by name, a run refuses a device that is not a TPU, a
checkout without the program cannot run, and the copied data generator
and LDA partition are deterministic from the seed."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import data, harness  # noqa: E402
from bench.peaks import peaks  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_cell_loads_from_its_files():
    for w in SPEC["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell["config_spec"]["name"] == w["config"]
        assert {"n_clients", "clients_per_round", "batch_size"} \
            <= set(cell["traffic_spec"])
        assert set(cell["limits"]) >= {"update_gap", "change_gap",
                                       "uplink_level_gap"}
        names = {m["name"] for m in cell["per_layer"]}
        assert names == {m["name"] for m in SPEC["per_layer"]}
        for m in cell["per_layer"]:
            assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert (ROOT / "bench" / "models"
                / f"{cell['config_spec']['family']}.py").is_file()


def test_benchmark_json_keys_and_bounds():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert {"round_s", "uplink_bytes", "setup_s"} <= set(e2e)
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()


def test_cell_mix_and_metric_added_as_files_only(tmp_path):
    """A new configuration, traffic mix, limits and per-layer metric are
    picked up from files alone, with no edit to the harness."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    spec = json.loads(json.dumps(SPEC))
    cfg = json.loads((ROOT / SPEC["configs"][0]["file"]).read_text())
    cfg["name"] = "resnet8_r16_int4"
    cfg["lora_rank"], cfg["quant_bits"] = 16, 4
    (tmp_path / "bench/configs/resnet8_r16_int4.json").write_text(
        json.dumps(cfg))
    mix = json.loads((ROOT / "bench/traffic/lda100_k10.json").read_text())
    mix["clients_per_round"] = 20
    (tmp_path / "bench/traffic/lda100_k20.json").write_text(json.dumps(mix))
    (tmp_path / "bench/limits/resnet8_r16_int4.lda100_k20.json").write_text(
        (ROOT / "bench/limits/resnet8_r32_int8.lda100_k10.json").read_text())
    (tmp_path / "bench/metrics/engine.rounds_traced.py").write_text(
        "def read(ctx):\n    return float(ctx['rounds'])\n")
    spec["configs"].append({"name": "resnet8_r16_int4", "source": "x",
                            "file": "bench/configs/resnet8_r16_int4.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "resnet8_r16_int4.lda100_k20",
                              "config": "resnet8_r16_int4",
                              "traffic": "lda100_k20", "chips": 1,
                              "why": "x"})
    spec["per_layer"].append({"name": "engine.rounds_traced", "unit": "1",
                              "better": "higher", "source": "host_clock",
                              "layer": "engine", "moves": "round_s",
                              "workloads": ["resnet8_r16_int4.lda100_k20"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell("resnet8_r16_int4.lda100_k20", root=tmp_path)
    assert cell["config_spec"]["quant_bits"] == 4
    assert cell["traffic_spec"]["clients_per_round"] == 20
    assert [m["name"] for m in cell["per_layer"]] == ["engine.rounds_traced"]
    got = harness.read_metrics(cell, {"rounds": 4})
    assert got == {"engine.rounds_traced": {"value": 4.0, "unit": "1"}}
    # the existing cells do not gain it
    old = harness.load_cell(SPEC["workloads"][0]["name"], root=tmp_path)
    assert "engine.rounds_traced" not in {m["name"] for m in
                                          old["per_layer"]}


def test_a_reader_that_finds_nothing_leaves_its_metric_out():
    cell = harness.load_cell(SPEC["workloads"][0]["name"])
    cell["per_layer"] = [m for m in cell["per_layer"]
                         if m["name"] in ("engine.pack_s", "trainer_mfu")]
    ctx = {"spans": {}, "rounds": 3, "n_steps": [],
           "trace": {"programs": {}}}
    assert harness.read_metrics(cell, ctx) == {}


def test_a_traced_chip_run_refuses_a_metric_that_reads_nothing():
    """On the chip every metric the cell lists must be read: a reader
    that finds nothing there is an error, not a silent gap."""
    cell = harness.load_cell(SPEC["workloads"][0]["name"])
    cell["per_layer"] = [m for m in cell["per_layer"]
                         if m["name"] in ("engine.pack_s", "trainer_mfu")]
    ctx = {"spans": {"fl/pack": [0.5]}, "rounds": 2, "n_steps": [],
           "trace": {"programs": {}}}
    with pytest.raises(RuntimeError, match="trainer_mfu"):
        harness.read_metrics(cell, ctx, strict=True)
    assert harness.read_metrics(cell, ctx) == {
        "engine.pack_s": {"value": 0.25, "unit": "s/round"}}


def test_run_refuses_a_device_that_is_not_a_tpu(capsys):
    rc = harness.main(["--workload", SPEC["workloads"][0]["name"],
                       "--seed", str(2 ** 33 + 1), "--seconds", "1",
                       "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "TPU" in out.err


def test_checkout_without_the_program_exits_nonzero(tmp_path):
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    run = subprocess.run(
        SPEC["command"] + ["--workload", SPEC["workloads"][0]["name"],
                           "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert run.returncode != 0
    assert run.stdout == ""


def test_peaks_known_device_and_unknown_is_an_error():
    v5e = peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["source"]
    with pytest.raises(KeyError):
        peaks("TPU v99")


def test_images_are_deterministic_from_the_seed():
    a = data.make_images(2 ** 33 + 7, 40, 10, (8, 8, 3), 0.35)
    b = data.make_images(2 ** 33 + 7, 40, 10, (8, 8, 3), 0.35)
    c = data.make_images(2 ** 33 + 8, 40, 10, (8, 8, 3), 0.35)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    assert a[0].shape == (40, 8, 8, 3) and a[0].dtype == np.float32
    assert np.array_equal(np.bincount(a[1]), np.full(10, 4))


def test_images_are_their_class_template_shifted_and_noised():
    x, y = data.make_images(5, 200, 10, (8, 8, 3), 0.0)
    s_tmpl = data.substreams(5, 4)[0]
    bank = data.variants(data.templates(s_tmpl, 10, (8, 8, 3)))
    for img, label in zip(x, y):
        assert any(np.array_equal(img, v) for v in bank[label])


def test_lda_partition_is_the_programs_and_deterministic():
    from repro.data import lda_partition
    labels = np.repeat(np.arange(10), 50)
    a = data.lda_partition(labels, 20, 0.5, seed=3)
    b = data.lda_partition(labels, 20, 0.5, seed=3)
    ref = lda_partition(labels, 20, 0.5, seed=3)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert all(np.array_equal(x, y) for x, y in zip(a, ref))
    assert sorted(np.concatenate(a).tolist()) == list(range(500))


def test_client_datasets_follow_the_traffic_mix():
    mix = {"n_images": 400, "noise": 0.35, "lda_alpha": 0.5,
           "n_clients": 8, "partition_seed": 0}
    one = data.make_clients(11, mix, (8, 8, 3), 10)
    two = data.make_clients(11, mix, (8, 8, 3), 10)
    assert len(one) == 8
    assert sum(len(d["y"]) for d in one) == 400
    for d1, d2 in zip(one, two):
        assert np.array_equal(d1["x"], d2["x"])


def test_every_seed_gets_the_same_client_sizes_in_another_order():
    """The split comes from the mix's ``partition_seed``: another run
    seed changes the images and the clients' order, not their sizes or
    label mixes, so the cohort schedule and its programs stay the same."""
    mix = {"n_images": 2000, "noise": 0.35, "lda_alpha": 0.5,
           "n_clients": 20, "partition_seed": 0}
    a = data.make_clients(2 ** 33 + 1, mix, (4, 4, 3), 10)
    b = data.make_clients(2 ** 33 + 2, mix, (4, 4, 3), 10)

    def mixes(clients):
        return sorted(np.bincount(d["y"], minlength=10).tolist()
                      for d in clients)

    assert mixes(a) == mixes(b)
    assert [len(d["y"]) for d in a] != [len(d["y"]) for d in b]
    other = data.make_clients(2 ** 33 + 1, dict(mix, partition_seed=1),
                              (4, 4, 3), 10)
    assert mixes(other) != mixes(a)
