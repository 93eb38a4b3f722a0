"""Readings from which a cell's limits are set, in one process.

    python3 -m chipbench.calibrate --workload <name> --seeds 1,2,... \
        --control-seeds 1,2,3 [--out FILE]

For every seed it runs the cell as the benchmark does (weights and
inputs from the seed, the program's compiled entry, the checked steps
or the window's answers) with a window of one call, and reports the
numbers that decide `correct` against the family's float32 reference
(with ``--fault``, of the program with that fault planted, from
``chipbench/faults.py``).  For the control seeds it also reports the
readings of the family's control (``control`` of
``chipbench/families/<family>.py``; for Deformable-DETR the reference
computed in float8, e4m3) against the reference: the control has to
fail one of them.  The limits are then set between the program's
largest reading and the control's smallest (``chipbench/limits/``).

Not part of a benchmark run: it is how the limits' readings are taken
on the chip.
"""
from __future__ import annotations

import argparse
import json
import sys

from chipbench import catalog, faults
from chipbench import run as bench_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", default="0.001",
                    help="window per seed: long enough for an inference "
                         "cell to serve every distinct batch")
    ap.add_argument("--fault", default=None,
                    help="plant this fault of chipbench/faults.py in the "
                         "program and read it in place of a sound run")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    bench = catalog.benchmark()
    cell = catalog.workload(bench, args.workload)
    cfg = catalog.config(bench, cell["config"])
    traffic = catalog.traffic(cell["traffic"])
    mode = traffic["mode"]
    fam = catalog.family(cfg)
    compare = fam.compare
    readings = {}

    def record(*a, **k):
        numbers, r = compare(*a, **k)
        readings.update(r)
        return numbers, r

    fam.compare = record
    for seed in sorted(set(seeds) | control):
        row = {"workload": args.workload, "seed": seed}
        if args.fault:
            row["fault"] = args.fault

        def on_reference(ref, inputs, seed=seed, row=row):
            row["reference"] = ref.get("losses")
            if seed in control:
                row["control"] = fam.control(mode, cfg, traffic, inputs, ref)

        bench_run.run(bench_run.parse_args([
            "--workload", args.workload, "--seed", str(seed),
            "--seconds", args.seconds]), hooks={
                "on_reference": on_reference,
                **(faults.hooks(mode, args.fault) if args.fault else {})})
        if seed in seeds:
            row["program"] = dict(readings)
        print("CALIB " + json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
