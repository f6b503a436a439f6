"""Paper Fig. 3: convergence of FedAvg vs FLoCoRA (r=32, alpha=512) and
its 8/4/2-bit quantized variants on the synthetic task."""
import sys

from benchmarks.common import fl_experiment


def run(rounds: int = 10) -> list[str]:
    rows = []
    for name, kw in [
        ("fedavg", dict(mode="fedavg")),
        ("flocora_fp", dict(rank=32, alpha=512.0)),
        ("flocora_int8", dict(rank=32, alpha=512.0, quant_bits=8)),
        ("flocora_int4", dict(rank=32, alpha=512.0, quant_bits=4)),
        ("flocora_int2", dict(rank=32, alpha=512.0, quant_bits=2)),
        # beyond-paper: error feedback rescues int2
        ("flocora_int2_ef", dict(rank=32, alpha=512.0, quant_bits=2,
                                 error_feedback=True)),
    ]:
        res = fl_experiment(arch="resnet8", rounds=rounds, **kw)
        curve = [h.get("test_acc") for h in res["history"]
                 if "test_acc" in h]
        rows.append(f"fig3/{name},0,best_acc={res['best_acc']} "
                    f"curve={curve} tcc_mb={res['tcc_bytes'] / 1e6:.2f}")
    return rows


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    r = 10
    if "--rounds" in sys.argv:
        r = int(sys.argv[sys.argv.index("--rounds") + 1])
    print("\n".join(run(r)))
