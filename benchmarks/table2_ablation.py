"""Paper Table II: which layers must be trained densely alongside the
LoRA adapters. Synthetic-data reproduction of the ablation's ORDERING
(vanilla << +norms << +final-FC); absolute CIFAR-10 numbers are offline-
unreachable (EXPERIMENTS.md §Repro-validity)."""
import sys

from benchmarks.common import fl_experiment

CONFIGS = [
    ("vanilla", dict(stem_mode="lora", fc_mode="lora",
                     norms_trained=False)),
    ("plus_norms", dict(stem_mode="lora", fc_mode="lora",
                        norms_trained=True)),
    ("plus_final_fc", dict(stem_mode="dense", fc_mode="dense",
                           norms_trained=True)),
]


def run(rounds: int = 10) -> list[str]:
    rows = []
    accs = {}
    for name, kw in CONFIGS:
        res = fl_experiment(arch="resnet8", rank=32, alpha=512.0,
                            rounds=rounds, **kw)
        accs[name] = res["best_acc"]
        rows.append(f"table2/{name},0,best_acc={res['best_acc']}")
    ordered = (accs["vanilla"] <= accs["plus_final_fc"] + 0.02)
    rows.append(f"table2/ordering,0,"
                f"vanilla<=final_fc={'OK' if ordered else 'UNEXPECTED'}")
    return rows


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    r = 10
    if "--rounds" in sys.argv:
        r = int(sys.argv[sys.argv.index("--rounds") + 1])
    print("\n".join(run(r)))
