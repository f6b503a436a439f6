"""Paper Table I: trainable/total params of ResNet-8 vs LoRA rank."""
import jax

from repro.core.lora import LoRAConfig
from repro.models.resnet import ResNetConfig, init as rinit
from repro.utils.tree import tree_size

PAPER = {8: (69_450, "69.45K"), 16: (131_914, "131.92K"),
         32: (256_842, "256.84K"), 64: (506_698, "506.70K"),
         128: (1_006_410, "1.00M")}


def run() -> list[str]:
    rows = []
    k = jax.random.PRNGKey(0)
    p = rinit(k, ResNetConfig(arch="resnet8", mode="fedavg"))
    n = tree_size(p["train"])
    rows.append(f"table1/fedavg,0,{n} trained (paper 1.23M) "
                f"{'OK' if n == 1_227_594 else 'MISMATCH'}")
    for r, (expect, label) in PAPER.items():
        cfg = ResNetConfig(arch="resnet8",
                           lora=LoRAConfig(rank=r, alpha=16.0 * r))
        p = rinit(k, cfg)
        n = tree_size(p["train"])
        tot = n + tree_size(p["frozen"])
        rows.append(f"table1/flocora_r{r},0,trained={n} total={tot} "
                    f"(paper {label}) {'OK' if n == expect else 'MISMATCH'}")
    return rows


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("\n".join(run()))
