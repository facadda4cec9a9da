"""Readings behind the limits of ``correct``, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 4

For each seed: a short window of the cell at its own load, then the numbers
compared with the reference, for the program and for the control (the
reference computed in bfloat16, in the program's place), on the same
requests and through the same checks, so that each line says whether the
control comes out ``correct``.  The lower reading of a number is the
largest the program gives over the seeds; the upper the smallest the
control gives.  One JSON line per seed, then a summary line.  The
benchmark's own runs do not run this.
"""
import time

T_START = time.perf_counter()

import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness  # noqa: E402

NUMBERS = ("yhat_gap", "prob_gap", "stop_unjustified", "plan_invalid")


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description="Readings of program and control.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args()
    harness.require_chips(1)
    harness.use_compile_cache()
    program = {k: [] for k in NUMBERS}
    control = {k: [] for k in NUMBERS}
    verdicts = []
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(args.workload, seed, args.seconds, False,
                               t_start=time.perf_counter(), control=True)
        got = {k: res["checks"][k]["value"] for k in NUMBERS}
        ctl = {k: res["control"]["checks"][k]["value"] for k in NUMBERS}
        for k in NUMBERS:
            program[k].append(got[k])
            control[k].append(ctl[k])
        verdicts.append((res["correct"], res["control"]["correct"]))
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "control_correct": res["control"]["correct"],
                          "program": got, "control": ctl,
                          "served": res["attempted"] - res["failed"]}), flush=True)
    print(json.dumps({
        "workload": args.workload,
        "program_correct": sum(p for p, _ in verdicts),
        "control_correct": sum(c for _, c in verdicts),
        "runs": len(verdicts),
        "lower": {k: max(v) for k, v in program.items()},
        "upper": {k: min(v) for k, v in control.items()},
    }), flush=True)


if __name__ == "__main__":
    main()
