"""Paper Fig. 2: rank r x scaling alpha (2r vs 16r) vs FedAvg on the
synthetic task — reproduces the paper's claim that alpha=16r beats
alpha=2r for from-scratch small-model FL."""
import sys

from benchmarks.common import fl_experiment


def run(rounds: int = 10, ranks=(8, 32)) -> list[str]:
    rows = []
    base = fl_experiment(arch="resnet8", mode="fedavg", rounds=rounds)
    rows.append(f"fig2/fedavg,0,best_acc={base['best_acc']}")
    for r in ranks:
        for mult in (2, 16):
            res = fl_experiment(arch="resnet8", rank=r,
                                alpha=float(mult * r), rounds=rounds)
            rows.append(f"fig2/r{r}_alpha{mult}r,0,"
                        f"best_acc={res['best_acc']} "
                        f"msg_bytes={res['round_bytes'] // 2}")
    return rows


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    r = 10
    if "--rounds" in sys.argv:
        r = int(sys.argv[sys.argv.index("--rounds") + 1])
    print("\n".join(run(r)))
