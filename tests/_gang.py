"""Shared 2-controller gang launcher (no import-time side effects).

ONE home for the launch/drain protocol used by both
tests/test_multiprocess.py and the driver dryrun's leg 8
(__graft_entry__._dryrun_two_process) — this very protocol needed a
lockstep fix once (the stderr-pipe gang stall below), which is exactly
why it must not be duplicated.

Protocol invariants:
- fresh coordinator port per gang;
- env scrubbed of the parent's single-process platform pins
  (JAX_PLATFORMS / XLA_FLAGS) so the workers
  pick their own 4-device CPU config;
- stderr goes to FILES, not pipes: the parent drains the workers
  SEQUENTIALLY, so a chatty worker 1 (orbax/XLA warnings) can fill its
  64 KB stderr pipe while worker 0 is being read, block mid-step, and
  stall the whole gang at the next collective until the coordination
  barrier times out. stdout stays a pipe — it is one JSON line;
- workers are killed on ANY failure (a rendezvous deadlock must not
  outlive the caller).
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile


def launch_gang(argv_tail, timeout: float = 600.0):
    """Spawn 2 worker controllers (tests/_mp_worker.py) with the given
    extra argv and return both parsed JSON outputs."""
    here = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(here)
    worker = os.path.join(here, "_mp_worker.py")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    errs = [tempfile.NamedTemporaryFile("w+", suffix=f"-w{pid}.err",
                                        delete=False)
            for pid in range(2)]
    procs = [subprocess.Popen(
        [sys.executable, worker, f"127.0.0.1:{port}", "2", str(pid)]
        + [str(a) for a in argv_tail],
        stdout=subprocess.PIPE, stderr=errs[pid], text=True,
        env=env, cwd=repo) for pid in range(2)]
    outs = []
    try:
        for p, ef in zip(procs, errs):
            out, _ = p.communicate(timeout=timeout)
            if p.returncode != 0:
                ef.seek(0)
                raise AssertionError(
                    f"worker failed:\n{ef.read()[-3000:]}")
            outs.append(json.loads(out.strip().splitlines()[-1]))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for ef in errs:
            ef.close()
            try:
                os.unlink(ef.name)
            except OSError:
                pass
    return outs
