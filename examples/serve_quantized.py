"""Serving with quantized FLoCoRA adapters: the server ships int8/int4
adapter messages to an edge inference node, which dequantizes, MERGES
them into the frozen base (W* = W + (α/r)·AB — zero added latency,
paper §II-C) and serves via the shared ``serve.generate()`` loop.

Then the OTHER deployment shape: one base hosting MANY tenants'
adapters, where merging is impossible. The multi-tenant engine keeps
every adapter in its packed wire form (``serve.AdapterCache``) and
serves mixed-rank request batches through the fused
gather+dequant+matmul kernel — validated here against the merged
``dense_merge`` oracle.

    PYTHONPATH=src python examples/serve_quantized.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import messages
from repro.core.lora import LoRAConfig
from repro.core.quant import QuantConfig
from repro.models import lm as LM
from repro import serve
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    cfg = LM.LMConfig(name="edge-lm", n_layers=4, d_model=128, n_heads=4,
                      n_kv_heads=2, head_dim=32, d_ff=512, vocab=512,
                      lora=LoRAConfig(rank=8, alpha=128.0),
                      head_mode="lora")
    params = LM.init(jax.random.PRNGKey(0), cfg)
    frozen, train = params["frozen"], params["train"]
    # pretend the adapters were trained: give them nonzero values
    train = jax.tree.map(
        lambda x: x + 0.02 * jax.random.normal(jax.random.PRNGKey(1),
                                               x.shape, x.dtype), train)

    # --- the wire: server -> edge, int4 ---------------------------------
    qcfg = QuantConfig(bits=4)
    wire_bytes = messages.message_wire_bytes(train, qcfg)
    fp_bytes = messages.message_wire_bytes(train, QuantConfig())
    print(f"adapter download: {wire_bytes / 1e3:.1f} KB int4 "
          f"(vs {fp_bytes / 1e3:.1f} KB fp32, "
          f"{fp_bytes / wire_bytes:.1f}x)")
    train_edge = messages.roundtrip(train, qcfg)   # what the edge decodes

    # --- generate with the dequantized adapters (merged, single tenant) -
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (2, 12)), jnp.int32)
    toks, timing = serve.generate(frozen, train_edge, cfg, prompt, gen=9,
                                  max_seq=32)
    print("generated:", np.asarray(toks))
    print(f"  prefill {timing['prefill_s']:.2f}s, "
          f"{timing['decode_steps']} decode steps "
          f"{timing['decode_s']:.2f}s")

    # --- multi-tenant: many adapters, one base, no merging --------------
    # a fleet of 8 clients uplinks rank-4/rank-8 adapters for a 2-layer
    # (d, d) chain; the engine serves a mixed batch straight from the
    # packed wire bytes (dequant fused into the matmul)
    weights, store = serve.make_store(n_clients=8, d_model=cfg.d_model,
                                      n_layers=2, ranks=(4, 8), bits=4,
                                      seed=0)
    cache = serve.AdapterCache(capacity_bytes=1 << 20, qcfg=store.qcfg)
    engine = serve.AdapterServingEngine(weights, scale=0.5,
                                        qcfg=store.qcfg, cache=cache,
                                        fetch=store.fetch)
    cids = [0, 1, 2, 3, 4, 5, 6, 7]          # even: rank 4, odd: rank 8
    engine.admit(cids)
    x = jnp.asarray(rng.standard_normal((8, cfg.d_model)) * 0.5,
                    jnp.float32)
    y = engine.step(x, cids)
    y_oracle = engine.oracle_step(x, cids)    # per-row merged dense
    err = float(jnp.max(jnp.abs(y - y_oracle)))
    print(f"multi-tenant fused serving vs merged oracle "
          f"(8 tenants, ranks 4+8): maxerr={err:.2e}")
    print(f"  cache: {cache.stats()}")


if __name__ == "__main__":
    main()
