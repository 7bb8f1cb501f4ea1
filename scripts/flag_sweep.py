#!/usr/bin/env python
"""XLA flag sweep on the flagship bench (roofline push, VERDICT r4 #3).

Runs ``bench.py --peak-only`` in a subprocess per flag set (XLA flags
must be set before backend init) and reports img/s per variant. Only
flags that are semantics-preserving scheduling/memory knobs are tried;
the winner (if any beats baseline by >2%) is a candidate for bench.py's
default environment.

    python scripts/flag_sweep.py            # full sweep
    python scripts/flag_sweep.py baseline vmem64   # named subset

Not measured on today's code: the one attempt (2026-08-01) ran on a
stack whose client-side XLA refused every --xla_tpu_* flag, so no
variant ever reached the chip. On the directly-attached v5e the sweep
is expected to run as written.

This parent never initialises a jax backend (it would hold the chip
its bench.py children need): it only sets XLA_FLAGS and reads JSON.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VARIANTS = {
    "baseline": "",
    # More VMEM for fusions -> larger tiles -> fewer HBM round trips.
    "vmem64": "--xla_tpu_scoped_vmem_limit_kib=65536",
    "vmem96": "--xla_tpu_scoped_vmem_limit_kib=98304",
    # Aggressive fusion knobs.
    "fusion_all": "--xla_tpu_enable_aggressive_loop_fusion_layout_opt=true",
    "multioutput": "--xla_tpu_enable_multi_level_nested_loop_fusion=true",
    # Async/overlap knobs (mostly collectives; cheap to test).
    "latency_hiding": "--xla_tpu_enable_latency_hiding_scheduler=true",
}


def run_variant(name: str, flags: str) -> dict:
    env = dict(os.environ)
    base = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = (base + " " + flags).strip()
    out = subprocess.run(
        [sys.executable, "bench.py", "--peak-only"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=900)
    line = ""
    for ln in out.stdout.strip().splitlines()[::-1]:
        if ln.startswith("{"):
            line = ln
            break
    if not line:
        return {"variant": name, "error": out.stderr[-500:]}
    d = json.loads(line)
    return {"variant": name, "flags": flags,
            "img_per_sec": d["value"],
            "pct_of_roofline": d.get("pct_of_roofline")}


def main() -> None:
    names = sys.argv[1:] or list(VARIANTS)
    unknown = [n for n in names if n not in VARIANTS]
    if unknown:
        sys.exit(f"unknown variant(s) {unknown}; "
                 f"valid: {', '.join(VARIANTS)}")
    results = []
    for n in names:
        r = run_variant(n, VARIANTS[n])
        print(json.dumps(r), flush=True)
        results.append(r)
    ok = [r for r in results if "img_per_sec" in r]
    if ok:
        best = max(ok, key=lambda r: r["img_per_sec"])
        print(f"# best: {best['variant']} at {best['img_per_sec']} img/s")


if __name__ == "__main__":
    main()
