"""Compile the main-path Pallas kernels for a TPU v5e, without a chip.

The TPU compiler compiles for a described ``v5e:2x2`` topology that is
not attached, and refuses what the chip would refuse: casts Mosaic has
no rule for, blocks that break the (8, 128) tiling, kernels that
overflow VMEM. Interpret-mode tests cannot see any of that. Each test
lowers the wrapper a user calls (``kernels/ops.py``) at the widths the
FL round and the serving engine use, with the dispatch steered to its
TPU branch inside the test, and asserts the compiled program holds a
Mosaic kernel (``tpu_custom_call``). The codec kernels carry stable
names (``quant_pack_rows``, ``dequant_agg_rows``) whatever wrapper calls
them, since the benchmark's roofline readers find them by name.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro.kernels import ops

# the quickstart ResNet-8 (rank-32 adapters) flat layout: C_total rows x
# n_max levels per row, per wire width (core/flat.layout_for)
FLAT_LAYOUT = {8: (1610, 2560), 4: (1610, 3072)}
# the BENCH_7 serving shape: E staged slots, M rows, d model width
SERVE_E, SERVE_M, SERVE_D = 512, 64, 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:        # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_dispatch(monkeypatch):
    """Steer ``ops`` to its TPU branches; drop jit caches on both sides
    so no program traced for one dispatch serves the other. The
    persistent compile cache stays off: a program compiled for a
    described chip cannot be read back without one."""
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    jax.clear_caches()
    yield
    monkeypatch.undo()
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args, **static):
    compiled = jax.jit(lambda *a: fn(*a, **static)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("bits", [8, 4])
def test_quant_pack_rows_compiles_at_flat_layout(bits, one_chip,
                                                 tpu_dispatch):
    c, n = FLAT_LAYOUT[bits]
    compiled = _assert_kernel(ops.quant_pack_rows, _spec(one_chip, (c, n)),
                              _spec(one_chip, (c,), jnp.int32), bits=bits)
    assert "quant_pack_rows" in compiled.as_text()


@pytest.mark.parametrize("k", [5, 1024])
def test_dequant_agg_rows_compiles(k, one_chip, tpu_dispatch):
    """K=5 rides in one tile; K=1024 walks the K-tiled grid."""
    c, n = FLAT_LAYOUT[8]
    compiled = _assert_kernel(
        ops.dequant_agg_rows, _spec(one_chip, (k, c, n // 4), jnp.uint32),
        _spec(one_chip, (k, c)), _spec(one_chip, (k, c)),
        _spec(one_chip, (k,)), _spec(one_chip, (c,), jnp.int32), bits=8)
    assert compiled.memory_analysis() is not None
    assert "dequant_agg_rows" in compiled.as_text()


@pytest.mark.parametrize("bits", [8, 4])
def test_per_leaf_codec_kernels_compile(bits, one_chip, tpu_dispatch):
    """The per-leaf oracle codec's kernels at a ResNet-8 conv leaf's
    channel-first view (32 channels x 3*3*256 taps)."""
    c, n = 32, 3 * 3 * 256
    compiled = _assert_kernel(ops.quant_pack, _spec(one_chip, (c, n)),
                              bits=bits)
    # the kernel's own name, not its caller's
    assert "quant_pack_rows" in compiled.as_text()
    nw = -(-n // ops.lane_levels(bits)) * ops.lane_levels(bits) * bits // 32
    compiled = _assert_kernel(
        ops.dequant_agg, _spec(one_chip, (5, c, nw), jnp.uint32),
        _spec(one_chip, (5, c)), _spec(one_chip, (5, c)),
        _spec(one_chip, (5,)), bits=bits)
    assert "dequant_agg_rows" in compiled.as_text()


@pytest.mark.parametrize("r", [4, 8])
def test_multi_lora_matmul_compiles(r, one_chip, tpu_dispatch):
    e, m, d = SERVE_E, SERVE_M, SERVE_D
    _assert_kernel(ops.multi_lora_matmul, _spec(one_chip, (m, d)),
                   _spec(one_chip, (d, d)), _spec(one_chip, (e, d, r)),
                   _spec(one_chip, (e, r, d)),
                   _spec(one_chip, (m,), jnp.int32), s=0.5)


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("r", [4, 8])
def test_multi_lora_matmul_packed_compiles(r, bits, one_chip, tpu_dispatch):
    e, m, d = SERVE_E, SERVE_M, SERVE_D
    per = 32 // bits
    _assert_kernel(ops.multi_lora_matmul_packed, _spec(one_chip, (m, d)),
                   _spec(one_chip, (d, d)),
                   _spec(one_chip, (e, r, -(-d // per)), jnp.uint32),
                   _spec(one_chip, (e, r)), _spec(one_chip, (e, r)),
                   _spec(one_chip, (e, d, -(-r // per)), jnp.uint32),
                   _spec(one_chip, (e, d)), _spec(one_chip, (e, d)),
                   _spec(one_chip, (m,), jnp.int32), s=0.5, bits=bits)


def test_sharded_cohort_reduction_compiles_on_four_chips(topo,
                                                         tpu_dispatch):
    """The mesh-sharded reduction over a 4-chip ``clients`` mesh: the
    K-tiled kernel on every shard, one all-reduce across them."""
    mesh = Mesh(np.asarray(topo.devices), (ops.CLIENT_AXIS,))
    sh = NamedSharding(mesh, P(ops.CLIENT_AXIS))
    rep = NamedSharding(mesh, P())
    k, (c, n) = 512, FLAT_LAYOUT[8]
    fn = ops._sharded_agg_fn(mesh, ops.CLIENT_AXIS, 8, 8, None)
    compiled = fn.lower(_spec(sh, (k, c, n // 4), jnp.uint32),
                        _spec(sh, (k, c)), _spec(sh, (k, c)),
                        _spec(sh, (k,)),
                        _spec(rep, (c,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text
