#!/usr/bin/env python3
"""Read, in ONE process, what the limits of ``correct`` are set from:
for each seed the cell's program stage with a short window and then its
reference stage, with the lower-precision control on the first
``--control-seeds`` of them. Not run by the benchmark's own runs.

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --control fp8 --control-seeds 3 --seconds 2 --out chiprun_out/x.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, run  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control", default="fp8")
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--seconds", type=float, default=2.0)
    p.add_argument("--out", default="")
    a = p.parse_args(argv)
    cell = harness.load_cell(a.workload)
    rows = []
    for i, seed in enumerate(int(s) for s in a.seeds.split(",")):
        with tempfile.TemporaryDirectory(prefix="tpunet-cal-") as work:
            argv_i = ["--workload", a.workload, "--seed", str(seed),
                      "--seconds", str(a.seconds), "--trace", "0"]
            if i < a.control_seeds:
                argv_i += ["--control", a.control]
            args = run.parse(argv_i)
            args.workdir, args.t0 = work, time.time()
            ctx = run.context(args, cell)
            prog = run.run_stage(ctx, "program")
            ref = run.run_stage(ctx, "reference")
        row = {"seed": seed, "numbers": ref["numbers"],
               "control": ref.get("control"), "metrics": prog["metrics"],
               "leaf_gaps": ref.get("leaf_gaps"),
               "control_leaf_gaps": ref.get("control_leaf_gaps"),
               "bn_var_gaps": ref.get("bn_var_gaps"),
               "control_bn_var_gaps": ref.get("control_bn_var_gaps")}
        rows.append(row)
        harness.say("calibrate", json.dumps(
            {k: row[k] for k in ("seed", "numbers", "control")}))
        if a.out:
            harness.write_json(os.path.join(ROOT, a.out), rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())
