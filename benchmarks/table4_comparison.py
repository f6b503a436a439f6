"""Paper Table IV: ResNet-18 message sizes / TCC (byte-exact) and —
with --train — the accuracy comparison on the synthetic task."""
import sys

import jax

from repro.core import messages
from repro.core.lora import LoRAConfig
from repro.core.quant import QuantConfig
from repro.models.resnet import ResNetConfig, init as rinit

PAPER_MSG = {("fedavg", None): 44.7,
             (64, None): 9.2, (32, None): 4.6, (16, None): 2.4,
             (64, 8): 2.4, (32, 8): 1.2, (16, 8): 0.7}


def run(train: bool = False, rounds: int = 12) -> list[str]:
    rows = []
    k = jax.random.PRNGKey(0)
    for (r, bits), paper in PAPER_MSG.items():
        if r == "fedavg":
            p = rinit(k, ResNetConfig(arch="resnet18", mode="fedavg"))
        else:
            p = rinit(k, ResNetConfig(
                arch="resnet18", lora=LoRAConfig(rank=r, alpha=16.0 * r)))
        mb = messages.message_wire_bytes(p["train"],
                                         QuantConfig(bits=bits)) / 1e6
        tcc_gb = messages.tcc_bytes(p["train"], QuantConfig(bits=bits),
                                    700) / 1e9
        tag = "fedavg" if r == "fedavg" else \
            f"r{r}" + ("" if bits is None else f"_q{bits}")
        ok = abs(mb - paper) < 0.06
        rows.append(f"table4/{tag},0,msg={mb:.2f}MB tcc={tcc_gb:.2f}GB "
                    f"(paper {paper}MB) {'OK' if ok else 'MISMATCH'}")
    if train:
        from benchmarks.common import fl_experiment
        for r, bits in ((64, None), (64, 8), (32, 8)):
            res = fl_experiment(arch="resnet18", rank=r, quant_bits=bits,
                                rounds=rounds, lda_alpha=1.0,
                                n_train=2000, n_clients=20,
                                clients_per_round=4)
            rows.append(f"table4/train_r{r}_q{bits},0,"
                        f"best_acc={res['best_acc']}")
    return rows


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("\n".join(run(train="--train" in sys.argv)))
