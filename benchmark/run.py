#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run is this process, which never touches JAX, and two children that
hold the chip one after the other:

1. ``program``: the cell's runner builds the system under test, hands
   it the weights made from the seed, warms up this cell's shapes
   (set-up), measures for ``--seconds``, and leaves what ``correct``
   needs (the first steps' inputs and numbers, or a sample of the
   finished requests) in the run's scratch directory.
2. ``reference``: the configuration's plain reference, alone on the
   chip after the program's state is gone, recomputes those numbers in
   float32 and compares each with its limit (``workloads/<cell>.json``).

Its time is not in ``setup_s`` and its memory is not in
``memory_peak_bytes``. The last line of standard output is the result;
every earlier line starts with ``#``. ``--control <precision>`` (not
used by the driver) also prints what the lower-precision reference
would read, for setting limits.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

T0 = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", default="",
                   help="also print this lower precision's numbers")
    p.add_argument("--stage", default="", help=argparse.SUPPRESS)
    p.add_argument("--workdir", default="", help=argparse.SUPPRESS)
    p.add_argument("--t0", type=float, default=0.0, help=argparse.SUPPRESS)
    return p.parse_args(argv)


def context(args, cell: dict, rehearse: bool = False) -> dict:
    return {"cell": cell, "seed": args.seed, "seconds": args.seconds,
            "trace": bool(args.trace), "control": args.control,
            "workdir": args.workdir, "t0": args.t0 or T0,
            "rehearse": rehearse}


def run_stage(ctx: dict, stage: str) -> dict:
    """One stage in this process: what a child does, and what the tests
    call with ``rehearse`` set."""
    runner = harness.load_runner(ctx["cell"])
    out_path = os.path.join(ctx["workdir"], f"{stage}.json")
    if stage == "program":
        result = runner.program(ctx)
    elif stage == "reference":
        result = runner.reference(
            ctx, harness.load_json(os.path.join(ctx["workdir"],
                                                "program.json"), root=""))
    else:
        raise SystemExit(f"unknown stage {stage!r}")
    harness.write_json(out_path, result)
    return result


def final_line(cell: dict, trace: bool, prog: dict, ref: dict) -> dict:
    """The result: the cell's end-to-end metrics (``--trace 0``) or its
    per-layer metrics (``--trace 1``), as the manifest names them."""
    wanted = cell["per_layer"] if trace else cell["end_to_end"]
    metrics = {}
    for m in wanted:
        got = prog["metrics"].get(m["name"])
        if got is not None:
            metrics[m["name"]] = {"value": got, "unit": m["unit"]}
    out = {"correct": bool(ref["correct"]), "attempted": prog["attempted"],
           "failed": prog["failed"], "metrics": metrics,
           "device": prog["device"]}
    if trace and prog.get("breakdown"):
        out["breakdown"] = prog["breakdown"]
    return out


def main(argv=None) -> int:
    args = parse(argv)
    cell = harness.load_cell(args.workload)
    if args.stage:
        run_stage(context(args, cell), args.stage)
        return 0
    workdir = tempfile.mkdtemp(prefix="tpunet-bench-")
    try:
        for stage in ("program", "reference"):
            cmd = [sys.executable, os.path.abspath(__file__),
                   "--workload", args.workload, "--seed", str(args.seed),
                   "--seconds", str(args.seconds), "--trace", str(args.trace),
                   "--stage", stage, "--workdir", workdir, "--t0", repr(T0)]
            if args.control:
                cmd += ["--control", args.control]
            t = time.time()
            rc = subprocess.run(cmd, cwd=ROOT).returncode
            harness.say(f"stage {stage}: rc {rc}, {time.time() - t:.1f} s")
            if rc != 0:
                return rc if rc > 0 else 1
        prog = harness.load_json(os.path.join(workdir, "program.json"), root="")
        ref = harness.load_json(os.path.join(workdir, "reference.json"),
                                root="")
        print(json.dumps(final_line(cell, bool(args.trace), prog, ref)),
              flush=True)
        return 0
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
