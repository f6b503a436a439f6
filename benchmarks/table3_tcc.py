"""Paper Table III: total communication cost (TCC) of ResNet-8 for
FP/int8/int4/int2 over 100 rounds — byte-exact accounting."""
import jax

from repro.core import messages
from repro.core.lora import LoRAConfig
from repro.core.quant import QuantConfig
from repro.models.resnet import ResNetConfig, init as rinit

PAPER = {None: 205.47, 8: 55.56, 4: 30.15, 2: 17.44}


def run() -> list[str]:
    rows = []
    k = jax.random.PRNGKey(0)
    fedavg = rinit(k, ResNetConfig(arch="resnet8", mode="fedavg"))
    mb = messages.tcc_bytes(fedavg["train"], QuantConfig(), 100) / 1e6
    rows.append(f"table3/fedavg_fp,0,TCC={mb:.2f}MB (paper 982.07) "
                f"{'OK' if abs(mb - 982.07) < 0.02 else 'MISMATCH'}")
    flo = rinit(k, ResNetConfig(arch="resnet8",
                                lora=LoRAConfig(rank=32, alpha=512.0)))
    for bits, paper in PAPER.items():
        mb = messages.tcc_bytes(flo["train"], QuantConfig(bits=bits),
                                100) / 1e6
        tag = "fp" if bits is None else f"int{bits}"
        ok = abs(mb - paper) < 0.03
        rows.append(f"table3/flocora_{tag},0,TCC={mb:.2f}MB "
                    f"(paper {paper}) {'OK' if ok else 'MISMATCH'}")
    return rows


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("\n".join(run()))
