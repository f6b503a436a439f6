"""The TPU dispatch branches of ``core/flat.py`` and ``kernels/ops.py``,
run on the CPU through the Pallas kernels in interpret mode.

Off-TPU the flat codec and the ops wrappers lower to their jnp twins,
so without steering the branches the chip takes (rectangular
``quant_pack_rows``, the ``dequant_agg_rows`` kernel, the padded
multi-adapter matmuls) would never run before they reach a TPU. The
fixture below flips the dispatch inside the test: ``ops._interpret``
reports a TPU and every kernel the branches call runs interpreted.
Packed words must be bit-identical to the twin branch; aggregates and
decodes agree to fp32 tolerance.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregation, flat, messages
from repro.kernels import ops


def _interpreted(kernel):
    @functools.wraps(kernel)
    def run(*args, **kwargs):
        return kernel(*args, **{**kwargs, "interpret": True})
    return run


@pytest.fixture
def tpu_branches(monkeypatch):
    """Call to switch the dispatch to the TPU branches for the rest of
    the test. Jit caches are dropped on both sides of the switch: a
    program traced under one dispatch must not serve the other."""
    def switch():
        monkeypatch.setattr(ops, "_interpret", lambda: False)
        for name in ("quant_pack_pallas", "dequant_agg_rows_pallas",
                     "multi_lora_matmul_pallas",
                     "multi_lora_matmul_q_pallas"):
            monkeypatch.setattr(ops, name, _interpreted(getattr(ops, name)))
        jax.clear_caches()

    yield switch
    monkeypatch.undo()
    jax.clear_caches()


def _tree(seed: int):
    """Leaves whose row lengths differ (ragged flat rows), a stacked
    leaf and an fp passthrough vector."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {"a": jax.random.normal(ks[0], (37, 8)),
            "b": jax.random.normal(ks[1], (8, 21)),
            "conv": jax.random.normal(ks[2], (3, 3, 5, 6)),
            "norm": jax.random.normal(ks[3], (6,))}


def _wire(msg):
    """The serialized message as comparable (entry, buffer, bytes)."""
    return [(path, key, np.asarray(buf).tobytes())
            for path, bufs in messages.message_to_wire(msg)
            for key, buf in sorted(bufs.items())]


def _run_codec(bits: int, k: int):
    msgs = [flat.pack_flat(_tree(i), bits) for i in range(k)]
    w = jnp.linspace(0.5, 2.0, k)
    agg = flat.fedavg_packed_flat(msgs, w)
    acc = aggregation.StreamingFlatAccumulator.for_layout(msgs[0].layout)
    for m, wi in zip(msgs, np.asarray(w)):
        acc.fold(m, float(wi))
    return {"payload": [np.asarray(m.payload) for m in msgs],
            "scale": [np.asarray(m.scale) for m in msgs],
            "zp": [np.asarray(m.zp) for m in msgs],
            "wire": [_wire(m) for m in msgs],
            "decoded": jax.tree.map(np.asarray, msgs[0].unpack()),
            "agg": jax.tree.map(np.asarray, agg),
            "fold": jax.tree.map(np.asarray, acc.mean())}


@pytest.mark.parametrize("bits", [2, 4, 8])
def test_flat_codec_tpu_branch_matches_twin(bits, tpu_branches):
    twin = _run_codec(bits, k=3)
    tpu_branches()
    tpu = _run_codec(bits, k=3)
    for key in ("payload", "scale", "zp"):
        for got, want in zip(tpu[key], twin[key]):
            np.testing.assert_array_equal(got, want)
    assert tpu["wire"] == twin["wire"]
    for key in ("decoded", "agg", "fold"):
        jax.tree.map(lambda g, t: np.testing.assert_allclose(
            g, t, rtol=1e-5, atol=1e-6), tpu[key], twin[key])


def test_per_leaf_ops_tpu_branch_matches_twin(tpu_branches):
    """Per-leaf ``quant_pack`` / ``dequant_agg`` (the oracle codec) and
    the ragged ``dequant_agg_rows`` with a K-tiled walk."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(12, 300)), jnp.float32)
    nv = jnp.asarray(rng.integers(1, 300, 12), jnp.int32)
    twin_pack = ops.quant_pack_rows(jnp.pad(x, ((0, 0), (0, 212))), nv, 8)
    twin_agg = ops.dequant_agg_rows(
        jnp.stack([twin_pack[0]] * 5), jnp.stack([twin_pack[1]] * 5),
        jnp.stack([twin_pack[2]] * 5), jnp.arange(1.0, 6.0), nv, 8,
        block_k=2)
    leaf = ops.quant_pack(x, 4)
    leaf_agg = ops.dequant_agg(leaf[0][None], leaf[1][None], leaf[2][None],
                               jnp.ones((1,)), 4)
    tpu_branches()
    got_pack = ops.quant_pack_rows(jnp.pad(x, ((0, 0), (0, 212))), nv, 8)
    for g, t in zip(got_pack, twin_pack):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(t))
    got_agg = ops.dequant_agg_rows(
        jnp.stack([got_pack[0]] * 5), jnp.stack([got_pack[1]] * 5),
        jnp.stack([got_pack[2]] * 5), jnp.arange(1.0, 6.0), nv, 8,
        block_k=2)
    np.testing.assert_allclose(np.asarray(got_agg), np.asarray(twin_agg),
                               rtol=1e-5, atol=1e-6)
    # the per-leaf kernels always run interpreted off-TPU: same words
    got_leaf = ops.quant_pack(x, 4)
    np.testing.assert_array_equal(np.asarray(got_leaf[0]),
                                  np.asarray(leaf[0]))
    np.testing.assert_allclose(
        np.asarray(ops.dequant_agg(got_leaf[0][None], got_leaf[1][None],
                                   got_leaf[2][None], jnp.ones((1,)), 4)),
        np.asarray(leaf_agg), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("bits", [4, 8])
def test_multi_lora_tpu_branches_match_twins(bits, tpu_branches):
    """The padded TPU branches of ``multi_lora_matmul`` (fp stacks) and
    ``multi_lora_matmul_packed`` (wire-format slabs) at a row count that
    is not a multiple of the 8-row block."""
    rng = np.random.default_rng(bits)
    m, k, n, r, e = 11, 64, 128, 8, 5
    per = 32 // bits
    x = jnp.asarray(rng.standard_normal((m, k)) * 0.5, jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, n)) * 0.2, jnp.float32)
    a = jnp.asarray(rng.standard_normal((e, k, r)) * 0.1, jnp.float32)
    b = jnp.asarray(rng.standard_normal((e, r, n)) * 0.1, jnp.float32)
    ids = jnp.asarray(rng.integers(0, e, m), jnp.int32)
    aq = jnp.asarray(rng.integers(0, 2**32, (e, r, k // per),
                                  dtype=np.uint64).astype(np.uint32))
    bq = jnp.asarray(rng.integers(0, 2**32, (e, n, -(-r // per)),
                                  dtype=np.uint64).astype(np.uint32))
    side = [jnp.asarray(rng.uniform(0.001, 0.01, s), jnp.float32)
            for s in ((e, r), (e, r), (e, n), (e, n))]
    packed = (aq, side[0], side[1], bq, side[2], side[3])
    twin_fp = ops.multi_lora_matmul(x, w, a, b, ids, 0.5)
    twin_q = ops.multi_lora_matmul_packed(x, w, *packed, ids, 0.5, bits)
    tpu_branches()
    got_fp = ops.multi_lora_matmul(x, w, a, b, ids, 0.5)
    got_q = ops.multi_lora_matmul_packed(x, w, *packed, ids, 0.5, bits)
    assert got_fp.shape == twin_fp.shape == (m, n)
    np.testing.assert_allclose(np.asarray(got_fp), np.asarray(twin_fp),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_q), np.asarray(twin_q),
                               rtol=1e-6, atol=1e-6)
