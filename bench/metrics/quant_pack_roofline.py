"""Percent of its roofline that the uplink (and downlink) pack kernel
reaches: the least time at the HBM peak over the kernel's device time.

Per call the kernel must read each quantized tensor's float32 values and
write its words and a float32 scale and zero point per channel; the bytes
are counted from the real row lengths, not the kernel's padding. It does a
few vector operations per value and no matrix work worth the MXU's peak,
so the bound is the bytes. Calls are the kernel's events in the trace."""
import math


def _is_kernel(name: str) -> bool:
    return "quant_pack" in name and "dequant" not in name


def read(ctx):
    ops = ctx["trace"]["ops"]
    names = [n for n in ops if _is_kernel(n)]
    t = sum(ops[n] for n in names)
    calls = sum(ctx["trace"]["op_counts"][n] for n in names)
    if t <= 0 or not calls:
        return None
    bits = ctx["bits"]
    per_call = sum(c * n * 4 + c * math.ceil(n * bits / 32) * 4 + 8 * c
                   for c, n in ctx["codec_rows"])
    return 100.0 * calls * per_call / ctx["peaks"]["hbm_bytes_per_s"] / t
