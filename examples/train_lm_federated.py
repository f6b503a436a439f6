"""End-to-end driver: federated FROM-SCRATCH training of a ~100M-param
decoder LM with FLoCoRA — frozen random base, LoRA adapters + norms
trained, int8 adapter exchange between 8 clients.

Default runs a reduced config for CI speed; ``--full`` uses the ~110M
config (12L x 768, 32k vocab) for a few hundred steps as in the
deliverable.

    PYTHONPATH=src python examples/train_lm_federated.py \
        [--rounds 4] [--local-steps 8] [--full]
"""
import argparse
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "src")

from repro.core import aggregation, messages
from repro.core.flocora import FLoCoRAConfig
from repro.core.lora import LoRAConfig
from repro.core.quant import QuantConfig
from repro.data.synthetic import markov_lm_batch
from repro.models import lm as LM
from repro.optim import sgd
from repro.utils.tree import tree_size
from repro.utils.compile_cache import enable_compile_cache


def make_cfg(full: bool) -> LM.LMConfig:
    if full:   # ~110M params
        return LM.LMConfig(name="lm-110m", n_layers=12, d_model=768,
                           n_heads=12, n_kv_heads=4, head_dim=64,
                           d_ff=3072, vocab=32768,
                           lora=LoRAConfig(rank=16, alpha=256.0),
                           head_mode="lora")
    return LM.LMConfig(name="lm-tiny", n_layers=4, d_model=128, n_heads=4,
                       n_kv_heads=2, head_dim=32, d_ff=512, vocab=512,
                       lora=LoRAConfig(rank=8, alpha=128.0),
                       head_mode="lora")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=8)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()

    cfg = make_cfg(args.full)
    fcfg = FLoCoRAConfig(rank=cfg.lora.rank, alpha=cfg.lora.alpha,
                         quant_bits=8)
    params = LM.init(jax.random.PRNGKey(0), cfg)
    frozen, gtrain = params["frozen"], params["train"]
    n_total = tree_size(frozen) + tree_size(gtrain)
    n_train = tree_size(gtrain)
    msg = messages.message_wire_bytes(gtrain, fcfg.qcfg)
    full_msg = (n_total) * 4
    print(f"params: total={n_total/1e6:.1f}M trainable={n_train/1e6:.2f}M "
          f"({100*n_train/n_total:.1f}%)")
    print(f"round message: {msg/1e6:.2f} MB vs full-model "
          f"{full_msg/1e6:.1f} MB -> {full_msg/msg:.1f}x reduction")

    opt = sgd(momentum=0.9)

    @jax.jit
    def local_train(train0, tokens):
        state = opt.init(train0)

        def step(carry, batch):
            tr, st = carry
            loss, g = jax.value_and_grad(
                lambda t: LM.loss_fn(frozen, t, cfg, {"tokens": batch})[0]
            )(tr)
            tr, st = opt.update(g, st, tr, 0.05)
            return (tr, st), loss

        (tr, _), losses = jax.lax.scan(step, (train0, state), tokens)
        return tr, losses.mean()

    rng = np.random.default_rng(0)
    for rnd in range(args.rounds):
        g_bcast = messages.roundtrip(gtrain, fcfg.qcfg)   # server -> client
        client_trees, losses, sizes = [], [], []
        for c in range(args.clients):
            toks = np.stack([
                markov_lm_batch(rng, cfg.vocab, args.batch, args.seq,
                                seed=c)["tokens"]
                for _ in range(args.local_steps)])
            trained, loss = local_train(g_bcast, jnp.asarray(toks))
            client_trees.append(messages.roundtrip(trained, fcfg.qcfg))
            losses.append(float(loss))
            sizes.append(args.local_steps * args.batch * args.seq)
        stacked = aggregation.stack_trees(client_trees)
        gtrain = aggregation.fedavg(stacked, jnp.asarray(sizes, jnp.float32))
        print(f"round {rnd + 1}: mean client loss = {np.mean(losses):.4f} "
              f"(cumulative TCC {2 * (rnd + 1) * msg / 1e6:.2f} MB/client)")


if __name__ == "__main__":
    main()
