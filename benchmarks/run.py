"""Benchmark runner — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Fast checks (byte-exact table
reproductions, kernel micro, roofline summary) always run; the FL
training reproductions (Table II, Fig 2/3 — minutes of CPU) run with
``--train`` (and ``--rounds N`` to deepen them).

    PYTHONPATH=src python -m benchmarks.run [--train] [--rounds N]
"""
import sys
import traceback


def main() -> None:
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    train = "--train" in sys.argv
    rounds = 10
    if "--rounds" in sys.argv:
        rounds = int(sys.argv[sys.argv.index("--rounds") + 1])

    sections = []
    from benchmarks import table1_params, table3_tcc, table4_comparison, \
        kernel_bench, roofline_report
    sections.append(("table1", table1_params.run))
    sections.append(("table3", table3_tcc.run))
    sections.append(("table4", lambda: table4_comparison.run(train=False)))
    sections.append(("kernels", kernel_bench.run))
    sections.append(("roofline", roofline_report.run))
    if train:
        from benchmarks import table2_ablation, fig2_rank_alpha, \
            fig3_convergence
        sections.append(("table2", lambda: table2_ablation.run(rounds)))
        sections.append(("fig2", lambda: fig2_rank_alpha.run(rounds)))
        sections.append(("fig3", lambda: fig3_convergence.run(rounds)))

    print("name,us_per_call,derived")
    failures = 0
    for name, fn in sections:
        try:
            for row in fn():
                print(row, flush=True)
        except Exception as e:
            failures += 1
            print(f"{name}/ERROR,0,{type(e).__name__}: {e}", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
