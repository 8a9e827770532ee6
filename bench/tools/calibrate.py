#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell.

  python3 bench/tools/calibrate.py --workload <name> --seeds 1,2,3 \\
      --seconds 2 --faults 3 --out <file.jsonl>

In one process, for each seed: one run of the cell as ``bench/run.py``
makes it (a short window), whose compared numbers are the program's
readings; then, on the first ``--faults`` seeds, the same numbers for
the control (the reference with every matrix-multiply operand rounded
to float8 e4m3) and for faults planted in the reference (half of each
batch left out; one attention answer zeroed), each against the float32
reference on the same weights and batches.  One JSON line per seed.
The benchmark's own runs never run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    from harness import compare
    from harness.main import load_spec, resolve, run_cell, use_compile_cache
    r = resolve(load_spec(), args.workload)
    use_compile_cache()
    limits = compare.limits(args.workload)
    out = open(args.out, "a")
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        keep = {}
        t0 = time.perf_counter()
        res = run_cell(r, seed, args.seconds, False, t_start=t0,
                       limits=limits, chips=int(r["cell"]["chips"]),
                       keep=keep)
        line = {"seed": seed, "correct": res["correct"],
                "program": {k: v["value"] for k, v in res["checks"].items()},
                "worst": compare.worst_names(keep["program"],
                                             keep["reference"]),
                "gaps": {"first_grad": compare.tensor_gaps(
                             keep["program"]["first_grad"],
                             keep["reference"]["first_grad"]),
                         "change": compare.tensor_gaps(
                             keep["program"]["change"],
                             keep["reference"]["change"])},
                "metrics": {k: v["value"] for k, v in res["metrics"].items()},
                "run_s": time.perf_counter() - t0}
        if i < args.faults:
            ref, c, key, b = (keep["ref"], keep["config"], keep["key"],
                              keep["batches"])
            base = keep["reference"]
            t1 = time.perf_counter()
            line["control"] = compare.numbers(compare.reference_readings(
                ref, c, key, b, rnd=compare.fp8), base)
            line["half_batch"] = compare.numbers(compare.reference_readings(
                ref, c, key, compare.half_batch(b)), base)
            line["answer_altered"] = compare.numbers(
                compare.reference_readings(
                    ref, c, key, b, alter=compare.zero_first_block), base)
            line["faults_s"] = time.perf_counter() - t1
        out.write(json.dumps(line) + "\n")
        out.flush()
        print("calibrate:", json.dumps(line), flush=True)
        del keep
    return 0


if __name__ == "__main__":
    sys.exit(main())
