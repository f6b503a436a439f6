"""Jit'd public wrappers for the Pallas kernels.

Pad-to-alignment, channel-first reshaping from arbitrary tensors, and
backend dispatch: on TPU the kernels compile natively (Mosaic); on any
other backend they run in interpret mode — same kernel body, Python
execution — or lower to their bit-identical jnp twins inside the same
jitted program (``quant_pack_rows``, ``dequant_agg_rows``, the
multi-adapter matmuls), which the test-suite checks against the kernels.
"""
from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import ref
from repro.kernels.dequant_agg import dequant_agg_rows_pallas, pick_block_k
from repro.kernels.lora_matmul import lora_matmul_pallas, \
    multi_lora_matmul_pallas, multi_lora_matmul_q_pallas, row_matmul, F32
from repro.kernels.quant_pack import quant_pack_pallas

Array = jax.Array


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def lane_levels(bits: int) -> int:
    """Kernel column alignment in LEVELS: 32/bits levels per uint32 word
    x 128 lanes. The single source of truth for the codecs' payload
    padding (per-leaf ``messages._pack_rows`` and the flat layout's
    ``n_max`` must agree on it, or byte identity breaks)."""
    return (32 // bits) * 128


def _pad_to(x: Array, mult: int, axis: int) -> Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@partial(jax.jit, static_argnames=("bits", "block_c"))
def quant_pack(x2d: Array, bits: int, block_c: int = 8):
    """x2d: (C, N) channel-first fp32 view of a message tensor."""
    per = 32 // bits
    lane = per * 128
    xp = _pad_to(_pad_to(x2d, block_c, 0), lane, 1)
    packed, scale, zp = quant_pack_pallas(xp, bits, n_valid=x2d.shape[1],
                                          block_c=block_c,
                                          interpret=_interpret())
    c = x2d.shape[0]
    return packed[:c], scale[:c], zp[:c]


def _quant_pack_rows_jnp(x2d: Array, nv: Array, bits: int):
    """Bit-identical jnp twin of the ragged-row quant_pack kernel (same
    formulas elementwise, exact min/max reductions, same little-endian
    word packing)."""
    qmax = (1 << bits) - 1
    col = jax.lax.broadcasted_iota(jnp.int32, x2d.shape, 1)
    valid = col < nv[:, None]
    big = jnp.float32(3.4e38)
    x = x2d.astype(jnp.float32)
    xmin = jnp.minimum(jnp.min(jnp.where(valid, x, big), axis=1), 0.0)
    xmax = jnp.maximum(jnp.max(jnp.where(valid, x, -big), axis=1), 0.0)
    rng = xmax - xmin
    scale = jnp.where(rng > 0, rng * jnp.float32(1.0 / qmax), 1.0)
    zp = jnp.clip(jnp.round(-xmin / scale), 0, qmax)
    q = jnp.round(x / scale[:, None]) + zp[:, None]
    q = jnp.where(valid, jnp.clip(q, 0, qmax), 0).astype(jnp.uint32)
    return ref.pack_words(q, bits), scale, zp


@partial(jax.jit, static_argnames=("bits", "block_c"))
def quant_pack_rows(x2d: Array, n_valid: Array, bits: int,
                    block_c: int = 8):
    """Ragged-row variant for the flat-tree codec: ``n_valid`` is a (C,)
    int32 vector of per-row true lengths (rows are different leaves'
    channels, so their valid widths differ). Columns must already be
    padded to the kernel lane multiple (core/flat.py sizes the buffer).
    One launch packs the WHOLE message.

    Off-TPU this lowers to the bit-identical jnp twin INSIDE the same
    jitted program (still one dispatch): the interpret-mode grid walk
    scales with C_total and would tax exactly the per-message overhead
    the flat codec removes."""
    nv = jnp.asarray(n_valid, jnp.int32)
    if _interpret():
        return _quant_pack_rows_jnp(x2d, nv, bits)
    xp = _pad_to(x2d, block_c, 0)
    packed, scale, zp = quant_pack_pallas(xp, bits,
                                          n_valid=_pad_to(nv, block_c, 0),
                                          block_c=block_c)
    c = x2d.shape[0]
    return packed[:c], scale[:c], zp[:c]


@partial(jax.jit, static_argnames=("bits", "block_c", "block_k"))
def dequant_agg_rows(packed: Array, scale: Array, zp: Array,
                     weights: Array, n_valid: Array, bits: int,
                     block_c: int = 8,
                     block_k: int | None = None) -> Array:
    """Flat-tree cohort aggregate: packed (K, C, Nw), sidecars (K, C),
    per-row lengths (C,). ONE launch unpacks + dequantizes + reduces the
    whole K-client message set; row tails come back as exact zeros.
    ``block_k`` (default: VMEM-budget auto-pick) tiles the client dim so
    fleet-scale cohorts stream through a bounded working set.
    Off-TPU: the jnp twin inside the same program, K-chunked via scan
    past one tile so time stays linear in K and memory flat."""
    nv = jnp.asarray(n_valid, jnp.int32)
    w = weights.astype(jnp.float32)
    zpz = jnp.where(scale > 0, zp, 0.0)
    k, c, nw = packed.shape
    bk = pick_block_k(k, nw, bits, block_c) if block_k is None \
        else int(block_k)
    if _interpret():
        if k <= bk:
            lv = ref.unpack_words(packed, bits).astype(jnp.float32)
            deq = (lv - zpz[..., None]) * scale[..., None]
            out = jnp.einsum("k,kcn->cn", w, deq)
        else:
            nt = -(-k // bk)
            pc = _pad_to(packed, bk, 0).reshape(nt, bk, c, nw)
            sc = _pad_to(scale, bk, 0).reshape(nt, bk, c)
            zc = _pad_to(zpz, bk, 0).reshape(nt, bk, c)
            wc = _pad_to(w, bk, 0).reshape(nt, bk)

            def fold(acc, xs):
                p, s, z, wt = xs
                lv = ref.unpack_words(p, bits).astype(jnp.float32)
                deq = (lv - z[..., None]) * s[..., None]
                return acc + jnp.einsum("k,kcn->cn", wt, deq), None

            out, _ = jax.lax.scan(
                fold, jnp.zeros((c, nw * (32 // bits)), jnp.float32),
                (pc, sc, zc, wc))
        col = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
        return jnp.where(col < nv[:, None], out, 0.0)
    return dequant_agg_rows_pallas(packed, scale, zpz, w, nv, bits,
                                   block_c=block_c, block_k=bk)


# -- mesh-sharded cohort reduction (the scale-out layer) --------------------

CLIENT_AXIS = "clients"


@functools.lru_cache(maxsize=None)
def _sharded_agg_fn(mesh: Mesh, axis: str, bits: int, block_c: int,
                    block_k: int | None):
    spec = P(axis)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(spec, spec, spec, spec, P()), out_specs=P(),
             check_vma=False)
    def _local(p, s, z, w, nv):
        part = dequant_agg_rows(p, s, z, w, nv, bits, block_c=block_c,
                                block_k=block_k)
        return jax.lax.psum(part, axis)

    return jax.jit(_local)


def dequant_agg_rows_sharded(packed: Array, scale: Array, zp: Array,
                             weights: Array, n_valid: Array, bits: int,
                             mesh: Mesh, axis: str = CLIENT_AXIS,
                             block_c: int = 8,
                             block_k: int | None = None) -> Array:
    """``dequant_agg_rows`` with the K client dim sharded over ``axis``
    of ``mesh`` (``launch.mesh.make_client_mesh``): every device folds
    its local client shard through the K-tiled kernel and ONE psum
    combines the partial sums, so aggregate reduction bandwidth scales
    with the device count. K pads to the axis size with zero-weight
    phantom clients (exact-zero contributions)."""
    n_sh = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    k = packed.shape[0]
    if k % n_sh:
        packed = _pad_to(packed, n_sh, 0)
        scale = _pad_to(scale, n_sh, 0)
        zp = _pad_to(zp, n_sh, 0)
        weights = _pad_to(weights.astype(jnp.float32), n_sh, 0)
    fn = _sharded_agg_fn(mesh, axis, bits, block_c, block_k)
    return fn(packed, scale, zp, weights.astype(jnp.float32),
              jnp.asarray(n_valid, jnp.int32))


@partial(jax.jit, static_argnames=("bits", "block_c"))
def dequant_agg(packed: Array, scale: Array, zp: Array, weights: Array,
                bits: int, block_c: int = 8,
                n_valid: Array | None = None) -> Array:
    """Per-leaf cohort aggregate: packed (K, C, Nw), sidecars (K, C) ->
    (C, N) fp32 through the same kernel as :func:`dequant_agg_rows`.
    ``n_valid`` (optional (C,) vector) masks each row's tail to exact
    zero."""
    _, c, nw = packed.shape
    nv = jnp.full((c,), nw * (32 // bits), jnp.int32) if n_valid is None \
        else jnp.asarray(n_valid, jnp.int32)
    return dequant_agg_rows_pallas(packed, scale,
                                   jnp.where(scale > 0, zp, 0.0), weights,
                                   nv, bits, block_c=block_c,
                                   interpret=_interpret())


@partial(jax.jit, static_argnames=("s",))
def lora_matmul(x: Array, w: Array, a: Array, b: Array, s: float) -> Array:
    """Fused y = x@w + s*(x@a)@b. Pads r to 128 lanes; picks MXU-aligned
    blocks that divide the (padded) problem."""
    m, k = x.shape
    n = w.shape[1]
    r = a.shape[1]
    rp = max(128, ((r + 127) // 128) * 128)
    ap = _pad_to(a, rp, 1)
    bp = _pad_to(b, rp, 0)

    def blk(dim, target):
        t = min(target, dim)
        while dim % t:
            t //= 2
        return max(t, 1)

    bm, bn, bk = blk(m, 256), blk(n, 256), blk(k, 512)
    return lora_matmul_pallas(x, w, ap, bp, s, block_m=bm, block_n=bn,
                              block_k=bk, interpret=_interpret())


# -- batched multi-adapter serving matmuls (the multi-tenant read path) -----

def _blk(dim: int, target: int) -> int:
    t = min(target, dim)
    while dim % t:
        t //= 2
    return max(t, 1)


def _multi_lora_matmul_jnp(x: Array, w: Array, a_stack: Array,
                           b_stack: Array, ids: Array, s: float) -> Array:
    """Bit-identical jnp twin of the multi-adapter kernel (same gather
    semantics, same per-row dots, fp32 accumulation)."""
    acc = jnp.dot(x, w, precision=F32, preferred_element_type=jnp.float32)
    am = jnp.take(a_stack, ids, axis=0)                   # (M, K, R)
    bm = jnp.take(b_stack, ids, axis=0)                   # (M, R, N)
    h = row_matmul(x, am)
    y = row_matmul(h.astype(bm.dtype), bm)
    return (acc + s * y).astype(x.dtype)


@partial(jax.jit, static_argnames=("s",))
def multi_lora_matmul(x: Array, w: Array, a_stack: Array, b_stack: Array,
                      ids: Array, s: float) -> Array:
    """Batched multi-adapter  y[m] = x[m]@w + s*(x[m]@A[ids[m]])@B[ids[m]].

    ``a_stack`` (E, K, R) / ``b_stack`` (E, R, N) are a rank bucket's
    staged adapter slab; ``ids`` (M,) int32 picks each request row's
    slot. Off-TPU this lowers to the bit-identical jnp twin inside the
    same jitted program (the per-row gather walk would tax interpret
    mode with exactly the per-request overhead batching removes)."""
    ids = jnp.asarray(ids, jnp.int32)
    if _interpret():
        return _multi_lora_matmul_jnp(x, w, a_stack, b_stack, ids, s)
    m = x.shape[0]
    n = w.shape[1]
    out = multi_lora_matmul_pallas(_pad_to(x, 8, 0), w, a_stack, b_stack,
                                   _pad_to(ids, 8, 0), s, block_m=8,
                                   block_n=_blk(n, 256))
    return out[:m]


def _multi_lora_matmul_q_jnp(x: Array, w: Array, aq: Array, a_scale: Array,
                             a_zp: Array, bq: Array, b_scale: Array,
                             b_zp: Array, ids: Array, s: float,
                             bits: int) -> Array:
    """Bit-identical jnp twin of the fused wire-format kernel: gather
    PACKED words by row id, unpack + dequant + matmul in one program —
    the fp32 adapter values exist only as a transient inside the jit."""
    k = x.shape[1]
    r = a_scale.shape[1]
    xf = x.astype(jnp.float32)
    acc = jnp.dot(xf, w.astype(jnp.float32), precision=F32,
                  preferred_element_type=jnp.float32)
    aw = jnp.take(aq, ids, axis=0)                        # (M, R, KW)
    asc = jnp.take(a_scale, ids, axis=0)
    azp = jnp.take(a_zp, ids, axis=0)
    bw = jnp.take(bq, ids, axis=0)                        # (M, N, RW)
    bsc = jnp.take(b_scale, ids, axis=0)
    bzp = jnp.take(b_zp, ids, axis=0)
    la = ref.unpack_words(aw, bits)[..., :k].astype(jnp.float32)
    adeq = (la - azp[..., None]) * asc[..., None]         # (M, R, K)
    lb = ref.unpack_words(bw, bits)[..., :r].astype(jnp.float32)
    bdeq = (lb - bzp[..., None]) * bsc[..., None]         # (M, N, R)
    h = row_matmul(xf, adeq, contract=2)
    y = row_matmul(h, bdeq, contract=2)
    return (acc + s * y).astype(x.dtype)


@partial(jax.jit, static_argnames=("s", "bits"))
def multi_lora_matmul_packed(x: Array, w: Array, aq: Array, a_scale: Array,
                             a_zp: Array, bq: Array, b_scale: Array,
                             b_zp: Array, ids: Array, s: float,
                             bits: int) -> Array:
    """The FUSED wire-format serving matmul: adapters stay in the packed
    uint32 wire form (channel-first rows + fp32 scale/zp sidecars, the
    ``quant_pack``/``core/flat.py`` layout) and dequant fuses into the
    matmul — an uplinked adapter serves without ever materializing an
    fp32 adapter tree. Slab layout: aq (E, R, KW), sidecars (E, R);
    bq (E, N, RW), sidecars (E, N); compact word counts (KW*per >= K,
    RW*per >= R, zero tails). Rank-bucket padding rides rows with
    scale=0 sidecars (exact-zero contributions)."""
    ids = jnp.asarray(ids, jnp.int32)
    if _interpret():
        return _multi_lora_matmul_q_jnp(x, w, aq, a_scale, a_zp, bq,
                                        b_scale, b_zp, ids, s, bits)
    m = x.shape[0]
    n = w.shape[1]
    xp = _pad_to(x, 8, 0)
    idp = _pad_to(ids, 8, 0)
    out = multi_lora_matmul_q_pallas(xp, w, aq, a_scale, a_zp, bq,
                                     b_scale, b_zp, idp, s, bits,
                                     block_m=8, block_n=_blk(n, 256))
    return out[:m]


# ---------------------------------------------------------------------------
# Channel-first 2D views (the CANONICAL helpers — the codec's last-axis-
# channel convention; every kernel caller reshapes through these)
# ---------------------------------------------------------------------------

def to_channel_first_2d(x: Array, per_stack: bool = False) -> Array:
    """(..., C) -> (C, prod(...)): the channel-first 2D view matching the
    per-channel qparam groups. ``per_stack`` keeps a leading stack dim's
    slices as separate qparam rows ((s*C, n) for an (s, n, C) tensor)."""
    if per_stack and x.ndim >= 3:
        s = int(np.prod(x.shape[:-2]))
        x3 = jnp.swapaxes(x.reshape(s, x.shape[-2], x.shape[-1]), -1, -2)
        return x3.reshape(s * x.shape[-1], x.shape[-2])
    xm = jnp.moveaxis(x, -1, 0)
    return xm.reshape(x.shape[-1], -1)


def from_channel_first_2d(x2d: Array, shape: tuple,
                          per_stack: bool = False) -> Array:
    """Inverse of :func:`to_channel_first_2d` for a target ``shape``."""
    if per_stack and len(shape) >= 3:
        s = int(np.prod(shape[:-2]))
        x3 = x2d.reshape(s, shape[-1], shape[-2])
        return jnp.swapaxes(x3, -1, -2).reshape(shape)
    x = x2d.reshape((shape[-1],) + tuple(shape[:-1]))
    return jnp.moveaxis(x, 0, -1)
