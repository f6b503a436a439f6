"""Pallas TPU kernels: fused LoRA matmuls.

Single-adapter (the FLoCoRA client forward hot loop):
  y = x@W + s*(x@a)@b.  The low-rank correction distributes over the K
(contraction) grid axis:  (x@a)@b = sum_k (x_k @ a_k) @ b, so each
(bm, bn, bk) step adds  x_k@w_k + s*(x_k@a_k)@b_n  into the fp32 output
block — no scratch, one epilogue-free accumulation loop, and the rank-r
side chain (r <= 128, one MXU pass) rides along with the dense matmul
instead of a separate XLA fusion with its own HBM round-trip.

Tiling: (M/bm, N/bn, K/bk) grid, K innermost; x (bm,bk), w (bk,bn),
a (bk,r), b (r,bn) tiles in VMEM; all matmul dims multiples of 128 for
the MXU (wrapper pads r up to 128 with zeros when needed).

Multi-adapter (the serving hot loop, multi-tenant read path):
  y[m] = x[m]@W + s * (x[m] @ A[ids[m]]) @ B[ids[m]] — every request row
gathers a DIFFERENT adapter from a stacked per-rank-bucket slab via a
per-row adapter-id vector. Two variants:

  * ``multi_lora_matmul_pallas`` — fp adapter stacks (the
    dequant-then-matmul baseline's second program);
  * ``multi_lora_matmul_q_pallas`` — adapter stacks in the PACKED WIRE
    FORMAT (uint32 little-endian words + per-channel fp32 scale/zp
    sidecars, exactly what ``core/flat.py`` rows / ``quant_pack`` emit):
    unpack + dequant FUSE into the matmul, so an uplinked adapter is
    servable without ever materializing an fp32 copy — the TensorRT-LLM
    weight-only-quant idiom. The gather moves packed words (4-8x fewer
    bytes than fp32) and dequantizes only the M gathered adapters, not
    the whole E-slot staged slab.

Both tile a (M/bm, N/bn) grid, full K per block (adapters quantize over
K per channel row, so K rides whole); the per-row gathers are static-
unrolled dynamic slices on the leading E dim of the VMEM-resident slab.
Off-TPU the jitted wrappers (ops.py) lower to bit-identical jnp twins
inside the same program, matching the quant_pack/dequant_agg pattern.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

# fp32 operands contract at fp32 on the MXU (Mosaic's default precision
# for a dot may round them to bf16); the jnp twins pass the same
F32 = jax.lax.Precision.HIGHEST


def _lora_matmul_kernel(x_ref, w_ref, a_ref, b_ref, out_ref, *, s: float):
    kk = pl.program_id(2)
    x = x_ref[...]
    acc = jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)
    h = jnp.dot(x, a_ref[...], preferred_element_type=jnp.float32)
    acc = acc + s * jnp.dot(h.astype(b_ref.dtype), b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(kk == 0)
    def _init():
        out_ref[...] = acc

    @pl.when(kk > 0)
    def _acc():
        out_ref[...] += acc


def lora_matmul_pallas(x: Array, w: Array, a: Array, b: Array, s: float, *,
                       block_m: int = 256, block_n: int = 256,
                       block_k: int = 512,
                       interpret: bool = False) -> Array:
    """x (M, K); w (K, N); a (K, r); b (r, N). Returns bf16 (M, N)."""
    m, k = x.shape
    n = w.shape[1]
    r = a.shape[1]
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    grid = (m // bm, n // bn, k // bk)
    out = pl.pallas_call(
        functools.partial(_lora_matmul_kernel, s=s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk, r), lambda i, j, kk: (kk, 0)),
            pl.BlockSpec((r, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, w, a, b)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Batched multi-adapter kernels (the multi-tenant serving read path)
# ---------------------------------------------------------------------------

def _gather_rows(ref, ids_ref, bm: int):
    """Static-unrolled per-row gather on the leading (adapter-slot) dim:
    rows of the block pick DIFFERENT adapters. ``ids`` rides as a
    (bm, 1) int32 block; each scalar drives one dynamic slice."""
    return jnp.concatenate(
        [ref[pl.ds(ids_ref[m, 0], 1)] for m in range(bm)], axis=0)


def row_matmul(x: Array, m: Array, contract: int = 1) -> Array:
    """Per-row matmul of x (bm, K) with m[i] (bm, K, N) contracted on
    dim ``contract`` (2 for a (bm, N, K) rhs), fp32 accumulate. The lhs
    rides as (bm, 1, K): Mosaic lowers a batched dot only when both
    operands carry the batch dim."""
    y = jax.lax.dot_general(x[:, None, :], m,
                            (((2,), (contract,)), ((0,), (0,))),
                            precision=F32,
                            preferred_element_type=jnp.float32)
    return y[:, 0, :]


def _multi_lora_matmul_kernel(ids_ref, x_ref, w_ref, a_ref, b_ref,
                              out_ref, *, s: float):
    x = x_ref[...]                                        # (bm, K)
    acc = jnp.dot(x, w_ref[...], precision=F32,
                  preferred_element_type=jnp.float32)
    bm = x.shape[0]
    am = _gather_rows(a_ref, ids_ref, bm)                 # (bm, K, R)
    bmat = _gather_rows(b_ref, ids_ref, bm)               # (bm, R, bn)
    h = row_matmul(x, am)
    y = row_matmul(h.astype(bmat.dtype), bmat)
    out_ref[...] = acc + s * y


def multi_lora_matmul_pallas(x: Array, w: Array, a_stack: Array,
                             b_stack: Array, ids: Array, s: float, *,
                             block_m: int = 8, block_n: int = 256,
                             interpret: bool = False) -> Array:
    """x (M, K); w (K, N); a_stack (E, K, R); b_stack (E, R, N);
    ids (M,) int32 adapter slots. Returns fp32 (M, N)."""
    m, k = x.shape
    n = w.shape[1]
    e, _, r = a_stack.shape
    bm, bn = min(block_m, m), min(block_n, n)
    assert m % bm == 0 and n % bn == 0
    grid = (m // bm, n // bn)
    out = pl.pallas_call(
        functools.partial(_multi_lora_matmul_kernel, s=s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, bn), lambda i, j: (0, j)),
            pl.BlockSpec((e, k, r), lambda i, j: (0, 0, 0)),
            pl.BlockSpec((e, r, bn), lambda i, j: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(ids.reshape(m, 1).astype(jnp.int32), x, w, a_stack, b_stack)
    return out.astype(x.dtype)


def _unpack_block(words: Array, bits: int):
    """(..., Nw) int32 words -> (..., Nw*per) fp32 levels, little-endian
    (broadcasted-iota shifts — the in-kernel twin of ref.unpack_words).
    Words ride as int32: Mosaic has no uint32 <-> f32 cast, and the
    mask drops the sign bits an arithmetic shift brings in."""
    per = 32 // bits
    shifts = jax.lax.broadcasted_iota(
        jnp.int32, (*words.shape, per), words.ndim) * bits
    lv = ((words[..., None] >> shifts) & ((1 << bits) - 1))
    return lv.astype(jnp.float32).reshape(
        *words.shape[:-1], words.shape[-1] * per)


def _multi_lora_matmul_q_kernel(ids_ref, x_ref, w_ref, aq_ref, as_ref,
                                az_ref, bq_ref, bs_ref, bz_ref, out_ref,
                                *, s: float, bits: int, k: int, r: int):
    x = x_ref[...].astype(jnp.float32)                    # (bm, K)
    acc = jnp.dot(x, w_ref[...].astype(jnp.float32), precision=F32,
                  preferred_element_type=jnp.float32)
    bm = x.shape[0]
    aw = _gather_rows(aq_ref, ids_ref, bm)                # (bm, R, KW)
    asc = _gather_rows(as_ref, ids_ref, bm)               # (bm, R)
    azp = _gather_rows(az_ref, ids_ref, bm)
    bw = _gather_rows(bq_ref, ids_ref, bm)                # (bm, bn, RW)
    bsc = _gather_rows(bs_ref, ids_ref, bm)               # (bm, bn)
    bzp = _gather_rows(bz_ref, ids_ref, bm)
    # dequant fused into the matmul: only the bm GATHERED adapters'
    # words unpack, and only transiently in VMEM — fp32 never lands
    adeq = (_unpack_block(aw, bits)[..., :k] - azp[..., None]) \
        * asc[..., None]                                  # (bm, R, K)
    bdeq = (_unpack_block(bw, bits)[..., :r] - bzp[..., None]) \
        * bsc[..., None]                                  # (bm, bn, R)
    h = row_matmul(x, adeq, contract=2)
    y = row_matmul(h, bdeq, contract=2)
    out_ref[...] = acc + s * y


def multi_lora_matmul_q_pallas(x: Array, w: Array, aq: Array, a_scale: Array,
                               a_zp: Array, bq: Array, b_scale: Array,
                               b_zp: Array, ids: Array, s: float,
                               bits: int, *, block_m: int = 8,
                               block_n: int = 256,
                               interpret: bool = False) -> Array:
    """Wire-format adapter slabs (channel-first rows, compact words):

      aq (E, R, KW) uint32  — A rows: R channels x K levels each;
      a_scale/a_zp (E, R)   — fp32 sidecars (padded bucket rows: 0/0);
      bq (E, N, RW) uint32  — B rows: N channels x R levels each;
      b_scale/b_zp (E, N).

    KW*per >= K and RW*per >= R (compact word counts; tails are zero
    levels by the codec's packing contract). Returns fp32 (M, N)."""
    m, k = x.shape
    n = w.shape[1]
    e, r, kw = aq.shape
    rw = bq.shape[2]
    per = 32 // bits
    assert kw * per >= k and rw * per >= r
    bm, bn = min(block_m, m), min(block_n, n)
    assert m % bm == 0 and n % bn == 0
    grid = (m // bm, n // bn)
    out = pl.pallas_call(
        functools.partial(_multi_lora_matmul_q_kernel, s=s, bits=bits,
                          k=k, r=r),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, bn), lambda i, j: (0, j)),
            pl.BlockSpec((e, r, kw), lambda i, j: (0, 0, 0)),
            pl.BlockSpec((e, r), lambda i, j: (0, 0)),
            pl.BlockSpec((e, r), lambda i, j: (0, 0)),
            pl.BlockSpec((e, bn, rw), lambda i, j: (0, j, 0)),
            pl.BlockSpec((e, bn), lambda i, j: (0, j)),
            pl.BlockSpec((e, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(ids.reshape(m, 1).astype(jnp.int32), x, w,
      jax.lax.bitcast_convert_type(aq, jnp.int32), a_scale, a_zp,
      jax.lax.bitcast_convert_type(bq, jnp.int32), b_scale, b_zp)
    return out.astype(x.dtype)
