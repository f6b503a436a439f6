"""Kernel microbenchmarks: Pallas (interpret on CPU — correctness-path
timing only; TPU is the compile target) vs the jnp reference path that
XLA would otherwise run. The derived column reports reconstruction error
and wire-bytes ratios (the quantities that matter for FLoCoRA)."""
import jax
import jax.numpy as jnp

from benchmarks.common import time_us
from repro.kernels import ops, ref


def run() -> list[str]:
    rows = []
    k = jax.random.PRNGKey(0)

    # quant_pack: adapter-message shaped (r=32 channels x d=4096)
    x = jax.random.normal(k, (32, 4096))
    for bits in (8, 4, 2):
        f_ref = jax.jit(lambda x, b=bits: ref.quant_pack_ref(x, b))
        us_ref = time_us(f_ref, x, iters=10)
        us_ker = time_us(lambda x, b=bits: ops.quant_pack(x, b), x, iters=3)
        packed, s, z = ops.quant_pack(x, bits)
        ratio = x.size * 4 / (packed.size * 4 + s.size * 8)
        rows.append(f"kernel/quant_pack_int{bits},{us_ref:.1f},"
                    f"jnp-ref-us={us_ref:.1f} pallas-interpret-us="
                    f"{us_ker:.1f} wire_compression={ratio:.2f}x")

    # dequant_agg: K=10 clients, one adapter tensor
    kc, c, n, bits = 10, 32, 4096, 8
    xs = jax.random.normal(k, (kc, c, n))
    packs = [ref.quant_pack_ref(xs[i], bits) for i in range(kc)]
    packed = jnp.stack([p[0] for p in packs])
    sc = jnp.stack([p[1] for p in packs])
    zp = jnp.stack([p[2] for p in packs])
    w = jnp.ones(kc) / kc
    f_ref = jax.jit(lambda: ref.dequant_agg_ref(packed, sc, zp, w, bits))
    us_ref = time_us(f_ref, iters=10)
    us_ker = time_us(lambda: ops.dequant_agg(packed, sc, zp, w, bits),
                     iters=3)
    rows.append(f"kernel/dequant_agg_k{kc},{us_ref:.1f},"
                f"jnp-ref-us={us_ref:.1f} pallas-interpret-us={us_ker:.1f} "
                f"fp32-copies-avoided={kc}")

    # lora_matmul
    m, kd, n, r = 256, 512, 512, 32
    x = (jax.random.normal(k, (m, kd)) * 0.5).astype(jnp.bfloat16)
    wmat = (jax.random.normal(k, (kd, n)) * 0.1).astype(jnp.bfloat16)
    a = (jax.random.normal(k, (kd, r)) * 0.1).astype(jnp.bfloat16)
    b = (jax.random.normal(k, (r, n)) * 0.1).astype(jnp.bfloat16)
    f_ref = jax.jit(lambda: ref.lora_matmul_ref(x, wmat, a, b, 2.0))
    us_ref = time_us(f_ref, iters=10)
    us_ker = time_us(lambda: ops.lora_matmul(x, wmat, a, b, 2.0), iters=3)
    extra = 2 * m * r * (kd + n) / (2 * m * n * kd)
    rows.append(f"kernel/lora_matmul_r{r},{us_ref:.1f},"
                f"jnp-ref-us={us_ref:.1f} pallas-interpret-us={us_ker:.1f} "
                f"lora_flop_overhead={extra * 100:.1f}%")
    return rows


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("\n".join(run()))
