"""Pallas TPU kernel: fused unpack + dequantize + weighted aggregate.

The FLoCoRA server hot loop: K quantized client messages -> one fp32
aggregated adapter tree, WITHOUT materializing K dequantized fp32 copies
(K x memory saved; the op is bandwidth-bound on the packed payload, which
is 4-16x smaller than fp32 — this fusion is what makes the paper's
quantization a server-side win too, not just a wire win).

The valid-column count is PER ROW: an (C,) int32 vector masks each row's
tail so a whole flat-tree message (every leaf's channel rows stacked into
one ragged buffer, core/flat.py) aggregates a K-client cohort in ONE
launch — contributions past a row's length are forced to exact zero, so
flat rows slice apart cleanly.

Grid ``(C/bc, K/bk)`` with K innermost: each step folds a ``bk``-client
tile into the fp32 output block resident in VMEM across the K walk, so
the working set is bounded by ``bk`` and throughput is flat in K.
``pick_block_k`` sizes ``bk`` from a VMEM budget; a cohort that fits
rides in one tile.

Clients accumulate STRICTLY SEQUENTIALLY (k=0..K-1): fp addition is
non-associative, so the walk is bit-identical to itself for EVERY
``bk`` — tiling the cohort never changes the result.

Layout on the TPU: a word's ``per`` levels are lane-interleaved, and
spreading them back out in the kernel is a shape cast Mosaic pads 32x
(a minor dim of ``per``). So the kernel never interleaves: level ``i``
of word ``j`` accumulates into output column ``i*Nw + j`` (whole
lane-aligned ``(bc, Nw)`` slabs, one per level position), and the
wrapper restores level order and masks the row tails in one XLA pass
over the (C, N) result. Words ride as int32 (Mosaic has no uint32 <->
f32 cast; the bitcast leaves the bits unchanged).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

# VMEM working-set budget for auto-picked client tiles (~half a v5e
# core's 16 MiB scoped VMEM, leaving room for the compiler's own use)
VMEM_BUDGET_BYTES = 8 * 1024 * 1024

# one (8, 128) fp32 VMEM tile: what each client's (bc, 1) sidecar and
# (1, 1) weight block occupies once laid out
_TILE_BYTES = 8 * 128 * 4


def pick_block_k(k: int, nw: int, bits: int, block_c: int = 8,
                 vmem_bytes: int = VMEM_BUDGET_BYTES) -> int:
    """Largest pow2 client tile whose double-buffered inputs — the
    packed ``(bk, bc, Nw)`` words plus the scale/zp/weight blocks, one
    VMEM tile each per client — and the double-buffered ``(bc, N)``
    output block fit the VMEM budget."""
    n = nw * (32 // bits)
    per_client = 2 * (block_c * nw * 4 + 3 * _TILE_BYTES)
    out_bytes = 2 * block_c * n * 4
    bk = max(1, (vmem_bytes - out_bytes) // per_client)
    bk = 1 << (int(bk).bit_length() - 1)
    return int(min(bk, max(int(k), 1)))


def _dequant_agg_rows_kernel(packed_ref, scale_ref, zp_ref, w_ref, out_ref,
                             *, bits: int):
    """Grid (C/bc, K/bk), K innermost. The fp32 output block stays
    resident in VMEM across the K walk; each step folds a bk-client
    tile into it, one client at a time in index order (the bit-parity
    contract across tile sizes)."""
    per = 32 // bits
    nw = packed_ref.shape[2]
    mask = (1 << bits) - 1

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def client(kk, carry):
        words = packed_ref[kk]                             # (bc, Nw) int32
        scale, zp, w = scale_ref[kk], zp_ref[kk], w_ref[kk]
        for i in range(per):
            lv = ((words >> (bits * i)) & mask).astype(jnp.float32)
            cols = slice(i * nw, (i + 1) * nw)
            out_ref[:, cols] = out_ref[:, cols] + w * ((lv - zp) * scale)
        return carry

    jax.lax.fori_loop(0, packed_ref.shape[0], client, 0)


def dequant_agg_rows_pallas(packed: Array, scale: Array, zp: Array,
                            weights: Array, n_valid: Array, bits: int, *,
                            block_c: int = 8,
                            block_k: int | None = None,
                            interpret: bool = False) -> Array:
    """packed (K, C, Nw) uint32; scale/zp (K, C); weights (K,);
    n_valid (C,) per-row true lengths. One launch aggregates the whole
    flat-tree cohort; tails past each row's length are exact zeros. C
    need not divide ``block_c``. ``block_k`` (default: VMEM-budget
    auto-pick) sizes the K tile; small cohorts ride in ONE tile (grid
    (C/bc, 1)). The result is bit-identical for every ``block_k``.
    Returns (C, N) fp32."""
    k, c, nw = packed.shape
    per = 32 // bits
    n = nw * per
    nv = jnp.asarray(n_valid, jnp.int32).reshape(c)
    bk = pick_block_k(k, nw, bits, block_c) if block_k is None \
        else int(block_k)
    bk = min(bk, k)
    weights = weights.astype(jnp.float32)
    k_pad = (-k) % bk
    if k_pad:
        # zero-weight phantom clients (scale 0 -> contribution exactly
        # +0.0) go AFTER the real fold sequence, so bit parity holds
        packed = jnp.pad(packed, ((0, k_pad), (0, 0), (0, 0)))
        scale = jnp.pad(scale, ((0, k_pad), (0, 0)))
        zp = jnp.pad(zp, ((0, k_pad), (0, 0)))
        weights = jnp.pad(weights, (0, k_pad))
    # a C that block_c does not divide ends in a partial row block: its
    # out-of-range rows read undefined values and are never written back
    out = pl.pallas_call(
        functools.partial(_dequant_agg_rows_kernel, bits=bits),
        grid=(pl.cdiv(c, block_c), (k + k_pad) // bk),   # K innermost:
        in_specs=[                          # the out block accumulates
            pl.BlockSpec((bk, block_c, nw), lambda i, t: (t, i, 0)),
            pl.BlockSpec((bk, block_c, 1), lambda i, t: (t, i, 0)),
            pl.BlockSpec((bk, block_c, 1), lambda i, t: (t, i, 0)),
            pl.BlockSpec((bk, 1, 1), lambda i, t: (t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_c, n), lambda i, t: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((c, n), jnp.float32),
        interpret=interpret,
        name="dequant_agg_rows",
    )(jax.lax.bitcast_convert_type(packed, jnp.int32), scale[..., None],
      zp[..., None], weights[:, None, None])
    # column i*Nw + j holds level i of word j: back to level order
    out = out.reshape(c, per, nw).swapaxes(1, 2).reshape(c, n)
    col = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
    return jnp.where(col < nv[:, None], out, 0.0)
