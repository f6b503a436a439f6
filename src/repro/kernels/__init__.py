"""Pallas TPU kernels for FLoCoRA's compute hot-spots.

  quant_pack   — fused per-channel affine quantize + bit-pack (uplink)
  dequant_agg  — fused unpack + dequantize + weighted aggregate (server)
  lora_matmul  — fused y = x@W + (α/r)(x@a)@b (client forward)

  multi_lora_matmul(_q) — batched multi-adapter matmuls (serving)

Each has a pure-jnp oracle in ref.py; tests sweep shapes/dtypes/bits in
interpret mode on the CPU, and tests/test_tpu_compile.py compiles the
kernels for a TPU v5e (the target) without a chip.
"""
from repro.kernels.ops import quant_pack, quant_pack_rows, dequant_agg, \
    dequant_agg_rows, lora_matmul, multi_lora_matmul, \
    multi_lora_matmul_packed, to_channel_first_2d, from_channel_first_2d
from repro.kernels import ref
