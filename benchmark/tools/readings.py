"""The readings that `correct`'s limits are set from, many seeds a process.

    python3 benchmark/tools/readings.py --workload <cell> --seeds 1,2,3 \
        [--controls fp8,bfloat16] [--faults half_batch] [--seconds 12]

For each seed: the program's numbers against the float32 reference (the
lower reading), then the reference at each `--controls` precision put in
the program's place, and with each `--faults` planted (the upper readings).
One JSON line each on stdout. A training cell needs no window; a serving
cell runs `--seconds` of its closed loop at the cell's own load.
Not part of a benchmark run: the builder runs it on the chip, PERF.md
records what it printed.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--leaves", help="a training cell: also write every "
                    "leaf's gradient and change norms, one JSON line a "
                    "candidate, to this file")
    args = ap.parse_args(argv)
    from benchmark import compare, run

    def say(**kw):
        print(json.dumps(kw), flush=True)

    def leaves(seed, kind, readings):
        if args.leaves:
            with open(args.leaves, "a") as f:
                f.write(json.dumps({
                    "seed": seed, "kind": kind, "loss": readings["loss"],
                    "grad_norm": readings["grad_norm"],
                    "delta_norm": readings["delta_norm"]}) + "\n")

    for seed in [int(s) for s in args.seeds.split(",") if s]:
        t0 = time.perf_counter()
        ctx = run.open_cell(args.workload, seed)
        if ctx is None:
            return 2
        runner = run.make_runner(ctx)
        runner.setup()
        training = ctx.workload["runner"] == "train"
        if not training:
            ctx.counters = runner.window(args.seconds)
        runner.release()
        gc.collect()
        t1 = time.perf_counter()
        numbers = runner.check()
        t2 = time.perf_counter()
        say(seed=seed, kind="program", numbers=numbers,
            worst=getattr(runner, "worst", None),
            losses=runner.readings["loss"] if training else None,
            ref_losses=runner.ref["loss"] if training else None,
            program_s=t1 - t0, reference_s=t2 - t1)
        if training:
            leaves(seed, "program", runner.readings)
            leaves(seed, "reference", runner.ref)
        for precision in [p for p in args.controls.split(",") if p]:
            if training:
                cand = runner.candidate(precision=precision)
                leaves(seed, "control:" + precision, cand)
                nums, worst = compare.train_numbers(cand, runner.ref)
            else:
                gaps = runner.gaps(precision=precision)
                nums, worst = {"token_gap": float(max(g.max() for g in gaps))
                               }, None
            say(seed=seed, kind="control:" + precision, numbers=nums,
                worst=worst)
        for fault in [f for f in args.faults.split(",") if f]:
            cand = runner.candidate(fault=fault)
            leaves(seed, "fault:" + fault, cand)
            nums, worst = compare.train_numbers(cand, runner.ref)
            say(seed=seed, kind="fault:" + fault, numbers=nums, worst=worst)
        del runner, ctx
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
