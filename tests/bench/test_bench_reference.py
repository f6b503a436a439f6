"""The benchmark's plain reference and yardstick on the CPU, at small
sizes: it computes the program's model, its codec's levels and bytes, its
FLOP formula agrees with XLA's count, and its feed check finds the faults
it names."""
import functools
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import data, harness, reference  # noqa: E402

CELLS = {"resnet8": "resnet8_r32_int8.lda100_k10",
         "resnet18": "resnet18_r64_int8.lda100_k10"}


def _cell(arch, image=None):
    cell = harness.load_cell(CELLS[arch])
    cell["config_spec"] = dict(cell["config_spec"])
    if image:
        cell["config_spec"]["image"] = [image, image, 3]
    return cell


def _weights(cell, seed=0, adapters=False):
    fam = harness.family(cell)
    w = jax.jit(functools.partial(fam.init, cfg=cell["config_spec"]))(
        jax.random.PRNGKey(seed))
    if adapters:    # give every A a value, so the side chains count
        keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
        w["train"] = jax.tree_util.tree_map_with_path(
            lambda p, x: 0.05 * jax.random.normal(next(keys), x.shape)
            if jax.tree_util.keystr(p).endswith("['a']") else x,
            w["train"])
    return w


@pytest.mark.parametrize("arch,paper_bytes", [("resnet8", 277_816),
                                              ("resnet18", 2_379_064)])
def test_wire_bytes_are_the_papers_and_the_programs(arch, paper_bytes):
    from repro.core import messages
    from repro.core.quant import QuantConfig
    cell = _cell(arch)
    shapes = jax.eval_shape(functools.partial(
        harness.family(cell).init, cfg=cell["config_spec"]),
        jax.random.PRNGKey(0))
    assert reference.wire_bytes(shapes["train"], 8) == paper_bytes
    assert messages.message_wire_bytes(shapes["train"],
                                       QuantConfig(bits=8)) == paper_bytes


@pytest.mark.parametrize("arch", ["resnet8", "resnet18"])
def test_reference_forward_is_the_programs_model(arch):
    cell = _cell(arch, image=8)
    cfg = cell["config_spec"]
    w = _weights(cell, adapters=True)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 8, 8, 3))
    y = jnp.arange(4) % 10
    with jax.default_matmul_precision("highest"):
        prog = harness.program_model(cell).loss_fn(cfg)(
            w["frozen"], w["train"], {"x": x, "y": y})[0]
    ref = harness.family(cell).loss(w["frozen"], w["train"], cfg, x, y,
                                    jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(float(prog), float(ref), rtol=1e-5)


def test_uplink_levels_match_the_programs_codec():
    from repro.core import flocora, messages
    from repro.core.flocora import FLoCoRAConfig
    cell = _cell("resnet8", image=8)
    cfg = cell["config_spec"]
    trees = [_weights(cell, seed=s, adapters=True)["train"] for s in (1, 2)]
    fcfg = FLoCoRAConfig(rank=cfg["lora_rank"], alpha=cfg["lora_alpha"],
                         quant_bits=8)
    wires = []
    for t in trees[::-1]:           # matched by scales, not by order
        msg, _ = flocora.client_uplink(t, fcfg)
        wires.append([b for n, b in messages.message_to_wire(msg)
                      if n != messages.HEADER_KEY])
    host = [jax.tree.map(np.asarray, t) for t in trees]
    assert reference.uplink_level_gap(host, wires, 8) <= 1
    i = next(j for j, b in enumerate(wires[0]) if "scale" in b)
    wires[0][i] = dict(wires[0][i], payload=wires[0][i]["payload"] ^ 0x80)
    assert reference.uplink_level_gap(host, wires, 8) >= 128


def test_roundtrip_levels_stay_in_range_and_zero_is_exact():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((3, 3, 16, 8)).astype(np.float32)
    q, scale, zp = reference.quantize_rows(reference.channel_rows(x), 4)
    assert q.min() >= 0 and q.max() <= 15
    back = reference.roundtrip({"w": x, "b": x[0, 0, 0]}, 4)
    assert np.abs(back["w"] - x).max() <= scale.max() / 2 + 1e-6
    assert np.array_equal(back["b"], x[0, 0, 0])
    z = reference.roundtrip({"w": np.zeros((2, 4), np.float32)}, 8)
    assert not z["w"].any()


def test_wire_levels_unpack_sub_byte_rows():
    levels = np.arange(12) % 16
    packed = np.packbits(
        ((levels[:, None] >> np.arange(4)) & 1).astype(np.uint8).reshape(-1),
        bitorder="little")
    got = reference.wire_levels(packed, 4, (3, 4))
    assert np.array_equal(got, levels.reshape(4, 3))


@pytest.mark.parametrize("arch", ["resnet8", "resnet18"])
def test_flop_formula_agrees_with_xla_on_one_step(arch):
    """XLA's count of one unmasked local step (forward, activation
    gradients, weight gradients of the trained leaves) exceeds the formula
    by the element-wise work the formula leaves out: 4.6% for ResNet-8
    and 2.9% for ResNet-18 at 8x8 images (GroupNorm, ReLU, pooling and
    softmax, which are a larger share of a small image's work). The frozen
    convolutions' weight gradient is in neither: counted, it would put
    the formula 20-40% above XLA."""
    cell = _cell(arch, image=8)
    cfg = cell["config_spec"]
    fam = harness.family(cell)
    w = _weights(cell, adapters=True)
    batch = 4
    x = jnp.zeros((batch, 8, 8, 3))
    y = jnp.zeros((batch,), jnp.int32)
    loss = harness.program_model(cell).loss_fn(cfg)
    grad = jax.jit(jax.grad(
        lambda t: loss(w["frozen"], t, {"x": x, "y": y})[0]))
    cost = grad.lower(w["train"]).compile().cost_analysis()
    xla = (cost[0] if isinstance(cost, list) else cost)["flops"]
    formula = fam.step_flops(cfg, batch)
    assert 1.0 <= xla / formula <= 1.08
    # the frozen convolutions' weight gradients: one more pass of their
    # multiply-adds
    frozen_dw, h = 0, 8
    for cin, width, s, proj in fam.blocks(cfg):
        frozen_dw += fam._taps(h, 3, s) ** 2 * cin * width
        if proj:
            frozen_dw += fam._taps(h, 1, s) ** 2 * cin * width
        h = -(-h // s)
        frozen_dw += fam._taps(h, 3, 1) ** 2 * width * width
    frozen_dw *= 2 * batch
    assert 0.2 <= frozen_dw / formula <= 0.4
    assert xla < formula + frozen_dw


def _clients():
    mix = {"n_images": 400, "noise": 0.35, "lda_alpha": 0.5, "n_clients": 6,
           "partition_seed": 0}
    return data.make_clients(9, mix, (8, 8, 3), 10)


def _staged(clients, cids, batch, steps):
    """What the cohort trainer is given: each slot one epoch of its
    client, then masked steps."""
    rng = np.random.default_rng(0)
    xs, ys, ns, idx = [], [], [], []
    for c in cids:
        n = len(clients[c]["y"])
        per = max(1, n // batch)
        perm = rng.permutation(n)
        if per * batch > n:
            perm = np.concatenate([perm, rng.integers(0, n, per * batch - n)])
        rows = perm[: per * batch].reshape(per, batch)
        pad = np.repeat(rows[:1], steps - per, axis=0)
        full = np.concatenate([rows, pad])
        xs.append(clients[c]["x"][full])
        ys.append(clients[c]["y"][full])
        ns.append(per)
        idx.append(rows)
    x = np.stack(xs)
    fp = x.reshape(*x.shape[:3], -1)[..., :reference.FINGERPRINT]
    return fp, np.stack(ys), np.asarray(ns), idx


def test_feed_is_recovered_and_its_faults_found():
    clients = _clients()
    index = reference.fingerprint_index(clients)
    batch = 8
    steps = max(max(1, len(d["y"]) // batch) for d in clients)
    fp, ys, ns, idx = _staged(clients, [4, 1], batch, steps)
    feed, faults = reference.recover_feed(index, clients, fp, ys, ns,
                                          batch, 1)
    assert faults == []
    assert [c for c, _ in feed] == [4, 1]
    assert all(np.array_equal(a, b) for (_, a), b in zip(feed, idx))
    # a skipped step, a wrong label, a client trained twice
    bad_ns = ns.copy()
    bad_ns[0] -= 1
    assert reference.recover_feed(index, clients, fp, ys, bad_ns, batch,
                                  1)[1]
    bad_y = ys.copy()
    bad_y[0, 0, 0] = (bad_y[0, 0, 0] + 1) % 10
    assert reference.recover_feed(index, clients, fp, bad_y, ns, batch,
                                  1)[1]
    fp2, ys2, ns2, _ = _staged(clients, [4, 4], batch, steps)
    assert reference.recover_feed(index, clients, fp2, ys2, ns2, batch,
                                  1)[1]


def test_norm_gaps_leave_out_leaves_the_reference_does_not_move():
    g0 = {"a": np.zeros(4), "b": np.zeros(4), "c": np.zeros(4)}
    ref = [{"a": np.ones(4), "b": np.full(4, 2.0), "c": np.full(4, 1e-9)}]
    prog = [{"a": np.full(4, 1.1), "b": np.full(4, 2.0), "c": np.ones(4)}]
    got = reference.training_checks(g0, prog, [1.0], ref, [1.0])
    assert got["leaves_left_out"] == ["['c']"]
    # leaf a: |1.1 - 1| * 2 / max(1, median 1.5 of a and b's norms)
    assert got["update_gap"] == pytest.approx(0.1 * 2 / 3)
    assert got["change_gap"] == pytest.approx(0.1 * 2 / 3 / 2)
    assert got["change_worst"] == got["update_gap"]
    assert got["change_leaf"] == "['a']"
    assert got["change_worst_rounds"] == [got["update_gap"]]
    assert got["loss_gap"] == 0.0
    same = reference.training_checks(g0, ref, [2.0], ref, [2.0])
    assert same["update_gap"] == 0.0 and same["change_gap"] == 0.0


def test_flop_formula_at_the_cells_sizes():
    got = {arch: harness.family(_cell(arch)).step_flops(
        _cell(arch)["config_spec"], 32) for arch in CELLS}
    assert got["resnet18"] / got["resnet8"] == pytest.approx(3.26, abs=0.01)
    assert json.dumps(got)
