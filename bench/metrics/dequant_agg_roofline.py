"""Percent of its roofline that the cohort aggregate kernel reaches: the
least time at the HBM peak over the kernel's device time.

Per call the kernel must read every kept client's words, scales, zero
points and weight, and write the float32 mean of each quantized tensor;
the bytes are counted from the real row lengths, not the kernel's
padding. Its arithmetic is a few vector operations per level, so the
bound is the bytes. Calls are the kernel's events in the trace; each
folds the round's cohort."""
import math


def read(ctx):
    ops = ctx["trace"]["ops"]
    names = [n for n in ops if "dequant_agg" in n]
    t = sum(ops[n] for n in names)
    calls = sum(ctx["trace"]["op_counts"][n] for n in names)
    if t <= 0 or not calls:
        return None
    bits, k = ctx["bits"], ctx["traffic"]["clients_per_round"]
    words = sum(c * math.ceil(n * bits / 32) * 4 + 8 * c
                for c, n in ctx["codec_rows"])
    out = sum(c * n * 4 for c, n in ctx["codec_rows"])
    per_call = k * (words + 4) + out
    return 100.0 * calls * per_call / ctx["peaks"]["hbm_bytes_per_s"] / t
