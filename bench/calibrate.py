"""Readings that the limits of ``correct`` are set from, for one cell:

    python3 bench/calibrate.py --workload <name> --seeds 1 2 ... [--control 3]
        [--faults half_cohort altered_uplink] [--out <file.jsonl>]

In one process, on the chip: a sound run of the program on each seed
(set-up, one window round, the reference and the checks), with the
bfloat16 control beside the reference on the first ``--control`` seeds,
then each planted fault (``harness.FAULTS``) on those seeds. One JSON
line per run goes to ``--out`` and a summary of each number to standard
output.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from bench import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", nargs="*", default=list(harness.FAULTS))
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("calibrate.py needs a TPU", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    cell = harness.load_cell(args.workload)
    runs = [(s, (), i < args.control) for i, s in enumerate(args.seeds)]
    runs += [(s, (f,), False) for f in args.faults
             for s in args.seeds[: args.control]]
    sink = open(args.out, "a") if args.out else None
    table: dict = {}
    for seed, faults, control in runs:
        out = harness.run_cell(cell, seed, 0.0, False, faults=faults,
                               control=control)
        line = {"workload": args.workload, "seed": seed,
                "faults": list(faults), "checks": out["checks"],
                "control": out.get("control"),
                "diagnostics": out["diagnostics"],
                "metrics": out["metrics"], "device": out["device"]}
        if sink:
            sink.write(json.dumps(line, default=str) + "\n")
            sink.flush()
        kind = "+".join(faults) or "sound"
        for name, c in out["checks"].items():
            table.setdefault((kind, name), []).append(c["value"])
        if out.get("control"):
            for name in ("loss_gap", *harness.TRAINING, "correct"):
                table.setdefault(("control", name), []).append(
                    out["control"][name])
        print(json.dumps({"seed": seed, "kind": kind,
                          "checks": {n: c["value"] for n, c in
                                     out["checks"].items()},
                          "correct": out["correct"],
                          "worst_leaf": out["diagnostics"]["change_leaf"],
                          "worst_rounds": out["diagnostics"][
                              "change_worst_rounds"],
                          "control": {n: out["control"][n] for n in
                                      ("loss_gap", *harness.TRAINING,
                                       "correct")}
                          if out.get("control") else None,
                          "setup_s": out["metrics"]["setup_s"]["value"]}),
              flush=True)
    for (kind, name), vals in sorted(table.items()):
        print(f"{kind:16s} {name:18s} n={len(vals):2d} min={min(vals)!r} "
              f"max={max(vals)!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
