"""Fleet-scale streaming aggregation (PR 6).

The acceptance contract of the streaming/K-tiled/sharded stack:
  * the K-tiled ``dequant_agg_rows`` kernel walk is BIT-IDENTICAL for
    every client-tile size ``block_k`` (the fp32 accumulator visits
    clients in the same order regardless of tiling); the dense jnp
    einsum is an independently-shaped numerics oracle (its reduction
    order differs -> tolerance, not bit, comparison);
  * the pallas entry point transparently pads a channel count that
    does not divide ``block_c`` (no caller-side alignment contract);
  * a ``StreamingFlatAccumulator`` folding arrivals one at a time
    matches the batched FedBuff flush across bits x density x
    heterogeneous ranks, steady-state folds compile ZERO new
    programs, and its checkpoint state round-trips bit-exactly;
  * every zero-weight flush RAISES (functional ``fedbuff_flush``, the
    streaming accumulator, and the buffered aggregator) — the old
    1e-8 floor silently emitted garbage trees;
  * the engine-level streaming path reproduces the batched engine's
    event history and final global tree, and a killed-then-resumed
    streaming run is bit-exact (slow-marked, with the sharded
    cohort-reduction subprocess test).
"""
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregation, flat, lora, messages
from repro.core.aggregation import FedBuffAggregator, \
    StreamingFlatAccumulator, fedbuff_add, fedbuff_flush, fedbuff_init
from repro.core.flocora import FLoCoRAConfig, RankSchedule
from repro.core.lora import LoRAConfig, linear_apply, linear_init
from repro.core.quant import QuantConfig
from repro.fl import AsyncConfig, AsyncFLServer, ClientConfig, \
    FleetTrace, LognormalLatency
from repro.kernels import ref as kref
from repro.kernels.dequant_agg import dequant_agg_rows_pallas, \
    pick_block_k

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# backend-compile counter: shared process-wide hook in repro.obs.compile
from repro.obs.compile import count_compiles  # noqa: E402


def _tree(seed: int, rank: int = 8, scale: float = 1.0):
    """Adapter-pair tree ({"a","b"} keys -> rank-bucketable) + an fp
    passthrough 1-D leaf, channel counts chosen NOT to divide 8."""
    k = jax.random.PRNGKey(seed)
    ks = jax.random.split(k, 3)
    return {"blk": {"a": jax.random.normal(ks[0], (13, rank)) * scale,
                    "b": jax.random.normal(ks[1], (rank, 21)) * scale},
            "norm": jax.random.normal(ks[2], (7,)) * scale}


def _flat_msgs(n: int, bits: int, rank: int = 8):
    qcfg = QuantConfig(bits=bits)
    return [messages.pack_message(_tree(i, rank), qcfg, flat=True)
            for i in range(n)]


def _stack(msgs):
    P = jnp.stack([m.payload for m in msgs])
    S = jnp.stack([m.scale for m in msgs])
    Z = jnp.stack([m.zp for m in msgs])
    nv = jnp.asarray(msgs[0].layout.n_valid_vec(), jnp.int32)
    return P, S, Z, nv


def _ref_agg(P, S, Z, w, nv, bits):
    """Dense jnp oracle of the rows kernel (zp zeroed like ops does)."""
    zpz = jnp.where(S > 0, Z, 0.0)
    lv = kref.unpack_words(P, bits).astype(jnp.float32)
    deq = (lv - zpz[..., None]) * S[..., None]
    out = jnp.einsum("k,kcn->cn", w.astype(jnp.float32), deq)
    col = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
    return jnp.where(col < nv[:, None], out, 0.0)


# ---------------------------------------------------------------------------
# K-tiled kernel: bit parity across tilings, one-tile oracle, ragged C
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [2, 4, 8])
def test_ktiled_bitwise_identical_across_block_k(bits):
    """The streaming K-tile walk must not change numerics with the tile
    size: every block_k gives the SAME bits (same fp32 visit order)."""
    msgs = _flat_msgs(13, bits)
    P, S, Z, nv = _stack(msgs)
    w = jnp.linspace(0.5, 2.0, 13)
    zpz = jnp.where(S > 0, Z, 0.0)
    outs = {bk: np.asarray(dequant_agg_rows_pallas(
        P, S, zpz, w, nv, bits, block_k=bk, interpret=True))
        for bk in (1, 2, 4, 8, 13, 16)}
    base = outs[13]                       # single tile covering all K
    for bk, o in outs.items():
        assert np.array_equal(o, base), f"block_k={bk} changed bits"
    np.testing.assert_allclose(
        base, np.asarray(_ref_agg(P, S, Z, w, nv, bits)),
        rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("bits", [4, 8])
def test_whole_k_kernel_is_tolerance_oracle(bits):
    """The whole cohort in ONE tile (grid (C/bc, 1)) against the tiled
    walk and the independently-shaped dense jnp oracle: the dense
    einsum reduces in its own order, so tolerance, not bit equality."""
    msgs = _flat_msgs(9, bits)
    P, S, Z, nv = _stack(msgs)
    w = jnp.linspace(0.5, 2.0, 9)
    zpz = jnp.where(S > 0, Z, 0.0)
    tiled = dequant_agg_rows_pallas(P, S, zpz, w, nv, bits,
                                    block_k=4, interpret=True)
    whole = dequant_agg_rows_pallas(P, S, zpz, w, nv, bits,
                                    block_k=9, interpret=True)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(tiled),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(whole), np.asarray(_ref_agg(P, S, Z, w, nv, bits)),
        rtol=1e-5, atol=1e-6)


def test_rows_kernel_transparent_c_padding():
    """C_total = 13 + 8 + 7(fp skipped) -> quantized rows don't divide
    block_c=8; the entry point must pad transparently and still match
    the dense oracle (no caller-side alignment assert)."""
    msgs = _flat_msgs(5, 4)
    P, S, Z, nv = _stack(msgs)
    assert P.shape[1] % 8 != 0            # the padding path is live
    w = jnp.ones((5,)) / 5
    zpz = jnp.where(S > 0, Z, 0.0)
    out = dequant_agg_rows_pallas(P, S, zpz, w, nv, 4, interpret=True)
    assert out.shape == P.shape[1:2] + (P.shape[2] * 8,)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_ref_agg(P, S, Z, w, nv, 4)),
        rtol=1e-5, atol=1e-6)


def test_pick_block_k_respects_vmem_budget():
    bk = pick_block_k(10_000, nw=32, bits=4)
    assert bk & (bk - 1) == 0             # pow2
    assert 1 <= bk <= 10_000
    # a tiny cohort never tiles past K
    assert pick_block_k(3, nw=32, bits=4) <= 3


# ---------------------------------------------------------------------------
# streaming accumulator vs batched flush: bits x density x hetero ranks
# ---------------------------------------------------------------------------

def _drive(agg: FedBuffAggregator, msgs, n_ks, stales):
    for m, n_k, s in zip(msgs, n_ks, stales):
        agg.add(m, n_k, s)
    return agg.flush()


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("ranks", [(8, 8, 8, 8), (4, 8, 4, 8)],
                         ids=["homo", "hetero"])
def test_streaming_matches_batched_flush(bits, ranks):
    """Per-arrival folds + O(1) normalize == buffered batched flush,
    for every wire width and across rank buckets (one stream per
    layout; layouts double as rank buckets)."""
    qcfg = QuantConfig(bits=bits)
    msgs = [messages.pack_message(_tree(i, r), qcfg, flat=True)
            for i, r in enumerate(ranks)]
    n_ks = [10.0, 20.0, 15.0, 5.0]
    stales = [0.0, 1.0, 3.0, 2.0]
    out_s = _drive(FedBuffAggregator(streaming=True, r_target=8),
                   [messages.pack_message(_tree(i, r), qcfg, flat=True)
                    for i, r in enumerate(ranks)], n_ks, stales)
    out_b = _drive(FedBuffAggregator(r_target=8), msgs, n_ks, stales)
    for a, b in zip(jax.tree.leaves(out_s), jax.tree.leaves(out_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-5, atol=1e-6)


def test_streaming_mixed_with_sparse_pending():
    """Sparse (density<1) uplinks are not flat messages: in streaming
    mode they still buffer in ``pending`` and a mixed flush recombines
    stream means and pending-bucket means by weight-mass fraction,
    matching the all-batched result."""
    qcfg = QuantConfig(bits=4)
    flat_m = [messages.pack_message(_tree(i), qcfg, flat=True)
              for i in range(2)]
    sparse_m = [messages.pack_message(_tree(i + 2), qcfg, density=0.5)
                for i in range(2)]
    msgs = [flat_m[0], sparse_m[0], flat_m[1], sparse_m[1]]
    n_ks = [10.0, 20.0, 15.0, 5.0]
    stales = [0.0, 1.0, 2.0, 0.0]
    s_agg = FedBuffAggregator(streaming=True, r_target=8)
    out_s = _drive(s_agg, msgs, n_ks, stales)
    assert not s_agg.pending and not s_agg.buffered
    out_b = _drive(FedBuffAggregator(r_target=8), msgs, n_ks, stales)
    for a, b in zip(jax.tree.leaves(out_s), jax.tree.leaves(out_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-5, atol=1e-6)


def test_streaming_folds_compile_zero_programs():
    """After the first fold compiles the per-layout program, further
    folds — with DIFFERENT weights and staleness — add nothing (the
    weight rides as a weak-typed traced scalar)."""
    msgs = _flat_msgs(6, 4)
    agg = FedBuffAggregator(streaming=True)
    agg.add(msgs[0], 1.0, 0.0)            # compiles the fold program
    jax.block_until_ready(next(iter(agg.streams.values())).acc)
    with count_compiles() as c:
        for i, m in enumerate(msgs[1:]):
            agg.add(m, 3.0 + i, float(i % 3))
        jax.block_until_ready(next(iter(agg.streams.values())).acc)
    assert c.count == 0
    assert agg.buffered == 6


def test_streaming_state_roundtrip_bit_exact():
    """Checkpointing the accumulator mid-buffer and restoring it must
    not perturb a single bit of the final mean."""
    msgs = _flat_msgs(5, 8)
    st = StreamingFlatAccumulator.for_layout(msgs[0].layout)
    for m in msgs[:3]:
        st.fold(m, 2.0)
    st2 = StreamingFlatAccumulator.from_state(msgs[0].layout, st.state())
    for s in (st, st2):
        for m in msgs[3:]:
            s.fold(m, 1.5)
    for a, b in zip(jax.tree.leaves(st.mean()),
                    jax.tree.leaves(st2.mean())):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# zero-weight flushes raise (the silent 1e-8 floor is gone)
# ---------------------------------------------------------------------------

def test_fedbuff_flush_zero_weight_raises():
    tree = _tree(0)
    state = fedbuff_init(tree)
    with pytest.raises(ValueError, match="zero accumulated weight"):
        fedbuff_flush(state, tree)
    # a weight-zero ADD (n_k=0) still leaves nothing to normalize by
    state = fedbuff_add(state, tree, jnp.asarray(0.0), jnp.asarray(0.0),
                        half_life=4.0)
    with pytest.raises(ValueError, match="zero accumulated weight"):
        fedbuff_flush(state, tree)


def test_streaming_accumulator_zero_weight_raises():
    msgs = _flat_msgs(1, 4)
    st = StreamingFlatAccumulator.for_layout(msgs[0].layout)
    with pytest.raises(ValueError, match="empty accumulator"):
        st.mean()
    st.fold(msgs[0], 0.0)
    with pytest.raises(ValueError, match="zero accumulated weight"):
        st.mean()


def test_aggregator_empty_and_zero_weight_flush_raise():
    agg = FedBuffAggregator(streaming=True)
    with pytest.raises(ValueError, match="empty buffer"):
        agg.flush()
    agg.add(_flat_msgs(1, 4)[0], 0.0, 0.0)     # discounted weight 0
    with pytest.raises(ValueError, match="zero accumulated weight"):
        agg.flush()


# ---------------------------------------------------------------------------
# engine level: streaming parity + bit-exact resume (slow)
# ---------------------------------------------------------------------------

SCALE = 1.0


def _lora_model(seed=0, rank=16):
    k = jax.random.PRNGKey(seed)
    fz, tr = linear_init(k, 16, 10, "lora",
                         LoRAConfig(rank=rank, alpha=float(rank)),
                         base_dtype=jnp.float32)
    return {"frozen": {"lin": fz},
            "train": {"lin": tr, "bias": jnp.zeros((10,))}}


def _lora_loss(frozen, train, batch):
    logits = linear_apply(frozen["lin"], train["lin"], batch["x"], SCALE,
                          jnp.float32) + train["bias"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None],
                                         axis=1)), {}


def _lin_data(n=240, n_clients=10, seed=0):
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=(16, 10)).astype(np.float32)
    x = rng.normal(size=(n, 16)).astype(np.float32)
    y = np.argmax(x @ w_true + 0.1 * rng.normal(size=(n, 10)),
                  axis=1).astype(np.int32)
    parts = np.array_split(rng.permutation(n), n_clients)
    return [{"x": x[p], "y": y[p]} for p in parts]


def _trace():
    return FleetTrace(seed=0, latency=LognormalLatency(
        compute_median_s=10.0, network_mbps=20.0))


HCFG = FLoCoRAConfig(rank=16, alpha=16.0, quant_bits=8,
                     rank_schedule=RankSchedule.tiered((8, 16), 10))


def _async_engine(streaming: bool, ckpt_dir=None):
    acfg = AsyncConfig(total_arrivals=30, concurrency=4, buffer_size=5,
                       microbatch_window=8.0, seed=0,
                       streaming_agg=streaming,
                       checkpoint_dir=ckpt_dir, checkpoint_every=2)
    return AsyncFLServer(_lora_model(rank=16), _lora_loss, _lin_data(),
                         acfg, ClientConfig(local_epochs=2, batch_size=8,
                                            lr=0.1),
                         HCFG, trace=_trace())


@pytest.mark.slow
def test_engine_streaming_parity_with_batched():
    """streaming_agg=True reproduces the batched engine's event
    schedule exactly (versions, virtual clock, wire bytes, staleness)
    and its global tree to fp tolerance (summation order differs)."""
    h_b = _async_engine(streaming=False)
    h_s = _async_engine(streaming=True)
    hist_b, hist_s = h_b.run(), h_s.run()
    assert len(hist_b) == len(hist_s) > 0
    for eb, es in zip(hist_b, hist_s):
        for key in ("version", "t_virtual", "tcc_bytes",
                    "staleness_mean"):
            assert eb[key] == es[key], key
    for a, b in zip(jax.tree.leaves(jax.device_get(h_b.global_train)),
                    jax.tree.leaves(jax.device_get(h_s.global_train))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-6)


@pytest.mark.slow
def test_streaming_resume_is_bit_exact(tmp_path):
    """ACCEPTANCE: killed-then-resumed STREAMING run == uninterrupted
    streaming run, bit for bit (checkpoints align to flush boundaries,
    so the restored accumulators are empty and re-fold identically)."""
    d_a, d_b = str(tmp_path / "a"), str(tmp_path / "b")
    srv_a = _async_engine(True, ckpt_dir=d_a)
    hist_a = srv_a.run()
    os.makedirs(d_b)
    for fn in os.listdir(d_a):
        shutil.copy(os.path.join(d_a, fn), d_b)
    steps = sorted(int(f[5:-5]) for f in os.listdir(d_b)
                   if f.endswith(".json"))
    assert len(steps) >= 2            # resume point strictly mid-run
    for s in steps[1:]:
        for ext in (".npz", ".json"):
            os.remove(os.path.join(d_b, f"ckpt_{s:08d}{ext}"))
    srv_b = _async_engine(True, ckpt_dir=d_b)
    assert srv_b.try_resume()
    assert srv_b.aggregator.buffered == 0
    hist_b = srv_b.run()
    assert hist_a == hist_b
    for a, b in zip(jax.tree.leaves(jax.device_get(srv_a.global_train)),
                    jax.tree.leaves(jax.device_get(srv_b.global_train))):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# sharded cohort reduction (8 fake devices, subprocess — device count
# locks at first jax init and the rest of the suite needs 1 device)
# ---------------------------------------------------------------------------

SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import aggregation, flat, messages
    from repro.core.quant import QuantConfig
    from repro.kernels import ops as kops
    from repro.launch.mesh import make_client_mesh

    def tree(i, rank=8):
        k = jax.random.PRNGKey(i)
        ks = jax.random.split(k, 3)
        return {"blk": {"a": jax.random.normal(ks[0], (13, rank)),
                        "b": jax.random.normal(ks[1], (rank, 21))},
                "norm": jax.random.normal(ks[2], (7,))}

    mesh = make_client_mesh()
    assert int(np.prod(mesh.devices.shape)) == 8
    for bits in (2, 8):
        qcfg = QuantConfig(bits=bits)
        # K=13: not a multiple of the axis -> phantom zero-weight pad
        for k in (13, 16):
            msgs = [messages.pack_message(tree(i), qcfg, flat=True)
                    for i in range(k)]
            w = jnp.linspace(0.5, 2.0, k)
            ref = aggregation.fedavg_packed(msgs, w)
            out = flat.fedavg_packed_flat_sharded(msgs, w, mesh)
            for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(out)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=3e-5, atol=1e-6)
            # kernel-level entry: sharded == single-device
            P = jnp.stack([m.payload for m in msgs])
            S = jnp.stack([m.scale for m in msgs])
            Z = jnp.stack([m.zp for m in msgs])
            nv = jnp.asarray(msgs[0].layout.n_valid_vec(), jnp.int32)
            r1 = kops.dequant_agg_rows(P, S, Z, w, nv, bits)
            r2 = kops.dequant_agg_rows_sharded(P, S, Z, w, nv, bits,
                                               mesh)
            np.testing.assert_allclose(np.asarray(r2), np.asarray(r1),
                                       rtol=1e-5, atol=1e-6)
    print("ALL_OK")
""")


@pytest.mark.slow
def test_sharded_cohort_reduction_matches_single_device():
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert "ALL_OK" in out.stdout, out.stdout[-2000:] + out.stderr[-2000:]


# ---------------------------------------------------------------------------
# flat wire padding strip: aligned + unaligned rows vs naive reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits,n_valid", [(8, 5), (4, 6), (4, 7),
                                          (2, 12), (2, 13)])
def test_strip_row_padding_matches_naive(bits, n_valid):
    """The byte-view fast path (n_valid*bits % 8 == 0) and the bit
    repack slow path must agree with the naive per-bit reference, and
    ``rows_from_wire`` must invert both (with the canonical zero
    tail) — including input wider than the row needs."""
    rng = np.random.default_rng(3)
    c, nw = 9, 4                           # wider than the row needs
    nww = (n_valid * bits + 31) // 32
    words = np.zeros((c, nw), np.uint32)
    lv = rng.integers(0, 1 << bits, (c, n_valid), dtype=np.uint32)
    for j in range(n_valid):               # pack the valid levels
        words[:, j * bits // 32] |= lv[:, j] << ((j * bits) % 32)
    words[:, nww:] = rng.integers(0, 2**32, (c, nw - nww),
                                  dtype=np.uint32)   # garbage past row
    wire = flat.strip_row_padding(words, bits, n_valid)
    # naive reference: per-level bit concat, little-endian
    nbits = n_valid * bits
    ref_bits = np.zeros((c, nbits), np.uint8)
    for j in range(n_valid):
        for t in range(bits):
            ref_bits[:, j * bits + t] = (lv[:, j] >> t) & 1
    ref = np.packbits(ref_bits.reshape(-1), bitorder="little")
    assert np.array_equal(wire, ref)
    back = flat.rows_from_wire(wire, bits, c, n_valid, nw)
    clean = words.copy()
    clean[:, nww:] = 0                     # canonical zero tail
    assert np.array_equal(back, clean)
