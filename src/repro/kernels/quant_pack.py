"""Pallas TPU kernel: fused per-channel affine quantize + bit-pack.

One VMEM pass per channel block: row min/max -> (scale, zp) -> RTN levels
-> little-endian pack into uint32 words. Replaces three XLA passes
(reduce, elementwise, gather/shift) with one streaming kernel — the
client-uplink hot loop is memory-bound, so the win is touching HBM once.

The valid-column count is PER ROW: ``n_valid`` rides as a tiny (C, 1)
int32 sidecar input (the SMEM-scalar-prefetch equivalent of the flat
codec's row-length vector) and masks both the qparam min/max reduction
and the packed tail of each row. A uniform tensor passes a constant
vector; the FLAT-TREE codec (core/flat.py) packs EVERY leaf of a message
as one ragged (C_total, N_max) buffer in a single launch, each row
masked to its own leaf's true length.

Tiling: grid over channel blocks; each step holds an (BC, N) fp32 tile
plus its (BC, N/per) word output in VMEM. BC=8 sublanes; N padded to a
multiple of 128*per by the wrapper (ops.py) so lanes stay aligned.

Packing on the TPU: gathering every ``per``-th lane into one word is a
lane-strided shape cast Mosaic refuses, so each 128-word output block is
an exact MXU product instead. The block's ``per*128`` levels (integers
<= 255, exact in bf16) multiply a 0/2^(bits*i) selection matrix in two
halves of ``per/2`` levels each, so every f32 accumulator holds at most
16 bits (exact); the halves combine in int32 as ``hi << 16 | lo``. The
kernel emits int32 words (Mosaic has no f32 <-> uint32 cast) and the
wrapper bitcasts them to the uint32 wire words: the little-endian
layout of ``ref.pack_words``, bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

Array = jax.Array


def _pack_selectors(bits: int) -> np.ndarray:
    """(2, per*128, 128) bf16-exact selection matrices: half ``h`` maps
    level ``i`` of word ``j`` (lane ``j*per + i`` of a block) to output
    lane ``j`` with weight ``2^(bits * (i - h*per/2))``."""
    per = 32 // bits
    half = per // 2
    row = np.arange(per * 128)[:, None]
    col = np.arange(128)[None, :]
    j, i = row // per, row % per
    out = np.zeros((2, per * 128, 128), np.float32)
    for h in range(2):
        hit = (j == col) & (i // half == h)
        out[h] = np.where(hit, 2.0 ** (bits * (i % half)), 0.0)
    return out


def _quant_pack_kernel(x_ref, nv_ref, sel_ref, packed_ref, scale_ref,
                       zp_ref, *, bits: int):
    x = x_ref[...].astype(jnp.float32)                    # (bc, N)
    n = x.shape[1]
    qmax = (1 << bits) - 1
    per = 32 // bits
    # mask each row's padded tail out of the min/max (pad value 0 is safe
    # for the affine range because 0 is always included, but stay exact)
    nv = nv_ref[...]                                      # (bc, 1) int32
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = col < nv
    big = jnp.float32(3.4e38)
    xmin = jnp.minimum(jnp.min(jnp.where(valid, x, big), axis=1), 0.0)
    xmax = jnp.maximum(jnp.max(jnp.where(valid, x, -big), axis=1), 0.0)
    rng = xmax - xmin
    # multiply by the f32 reciprocal constant instead of dividing by
    # qmax: XLA strength-reduces constant divisions inconsistently
    # across programs, and the flat codec's jnp twin must reproduce the
    # kernel's scale BIT-exactly
    scale = jnp.where(rng > 0, rng * jnp.float32(1.0 / qmax), 1.0)
    zp = jnp.clip(jnp.round(-xmin / scale), 0, qmax)
    q = jnp.round(x / scale[:, None]) + zp[:, None]
    # canonical zero padding past each row's n_valid: packed words are
    # byte-identical to the host/wire re-packing paths (messages/flat)
    q = jnp.where(valid, jnp.clip(q, 0, qmax), 0).astype(jnp.bfloat16)
    lo_sel, hi_sel = sel_ref[0], sel_ref[1]
    blk = per * 128
    for b in range(n // blk):               # one 128-word block at a time
        lv = q[:, b * blk:(b + 1) * blk]
        lo = jnp.dot(lv, lo_sel, preferred_element_type=jnp.float32)
        hi = jnp.dot(lv, hi_sel, preferred_element_type=jnp.float32)
        packed_ref[:, b * 128:(b + 1) * 128] = \
            (hi.astype(jnp.int32) << 16) | lo.astype(jnp.int32)
    scale_ref[...] = scale[:, None]
    zp_ref[...] = zp[:, None]


def quant_pack_pallas(x: Array, bits: int, *,
                      n_valid: int | Array | None = None,
                      block_c: int = 8, interpret: bool = False):
    """x: (C, N) fp32, N % (32/bits * 128) == 0 (wrapper pads).

    ``n_valid`` is the true (unpadded) column count — a scalar for a
    uniform tensor or a (C,) vector for a ragged flat-tree buffer.
    Columns past each row's count are excluded from the min/max and
    packed as level 0 (rows with ``n_valid == 0`` emit all-zero words
    with scale 1, zp 0 — the degenerate-channel convention).

    Returns (packed (C, N*bits/32) uint32, scale (C,), zp (C,))."""
    c, n = x.shape
    per = 32 // bits
    assert c % block_c == 0 and n % (per * 128) == 0
    if n_valid is None:
        n_valid = n
    if isinstance(n_valid, (int, np.integer)):
        assert 0 < n_valid <= n
        nv = jnp.full((c, 1), n_valid, jnp.int32)
    else:
        nv = jnp.asarray(n_valid, jnp.int32).reshape(c, 1)
    nw = n // per
    grid = (c // block_c,)
    packed, scale, zp = pl.pallas_call(
        functools.partial(_quant_pack_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_c, n), lambda i: (i, 0)),
            pl.BlockSpec((block_c, 1), lambda i: (i, 0)),
            pl.BlockSpec((2, per * 128, 128), lambda i: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_c, nw), lambda i: (i, 0)),
            pl.BlockSpec((block_c, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_c, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c, nw), jnp.int32),
            jax.ShapeDtypeStruct((c, 1), jnp.float32),
            jax.ShapeDtypeStruct((c, 1), jnp.float32),
        ],
        interpret=interpret,
        name="quant_pack_rows",
    )(x, nv, jnp.asarray(_pack_selectors(bits), jnp.bfloat16))
    packed = jax.lax.bitcast_convert_type(packed, jnp.uint32)
    return packed, scale[:, 0], zp[:, 0]
