#!/usr/bin/env python
"""Standing chaos matrix: four elastic failure legs, end-to-end on CPU.

Each leg drives the REAL stack — `python -m tpunet.main` children
under `tpunet/elastic/` agents, deterministic `--chaos` injection —
and asserts a successfully resumed completion under the original
run_id; the kill legs additionally assert a complete flight-recorder
crash report from the killed child. Wired into
`scripts/run_checks.sh --slow` (docs/elasticity.md "The standing
chaos matrix"); the two kill legs also run smaller in tier-1
(tests/test_elastic.py).

    python scripts/chaos_smoke.py                 # all four legs
    python scripts/chaos_smoke.py --legs sigterm_grace,slow_host_evict
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import threading
from typing import Dict, List, Optional

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _child_env(extra: Optional[Dict[str, Optional[str]]] = None
               ) -> Dict[str, Optional[str]]:
    from tpunet.utils.cache import cache_dir
    env: Dict[str, Optional[str]] = {
        "XLA_FLAGS": None,               # one CPU device per process
        "JAX_PLATFORMS": "cpu",
        "JAX_COMPILATION_CACHE_DIR": cache_dir(),
    }
    env.update(extra or {})
    return env


def _train_cmd(run_dir: str, chaos_spec: str, *, epochs: int = 3,
               batch: int = 16, synthetic: int = 64,
               extra: Optional[List[str]] = None) -> List[str]:
    return [
        sys.executable, "-m", "tpunet.main",
        "--dataset", "synthetic", "--image-size", "32",
        "--batch-size", str(batch), "--synthetic-size", str(synthetic),
        "--model", "vit", "--vit-patch", "8", "--vit-hidden", "32",
        "--vit-depth", "1", "--vit-heads", "2",
        "--dtype", "float32", "--dropout-rate", "0",
        "--epochs", str(epochs), "--checkpoint-dir", run_dir,
        "--no-native-loader", "--chaos", chaos_spec,
    ] + (extra or [])


def _run_gang(workdir: str, cmd: List[str], hosts: Dict[str, dict],
              env_extra: Optional[Dict[str, Optional[str]]] = None,
              join_timeout: float = 420.0) -> Dict[str, int]:
    """Run one agent per host in threads; return exit codes."""
    from tpunet.elastic.agent import AgentConfig, ElasticAgent
    run_dir = os.path.join(workdir, "run")
    rdzv_dir = os.path.join(workdir, "rdzv")
    rcs: Dict[str, int] = {}
    threads = []
    for host, kw in hosts.items():
        cfg = AgentConfig(
            run_dir=run_dir, rdzv_dir=rdzv_dir, host_id=host,
            command=cmd, settle_s=0.4, timeout_s=120.0, beat_s=0.1,
            dead_after_s=10.0, grace_s=3.0,
            env=_child_env(env_extra), **kw)
        t = threading.Thread(
            target=lambda h=host, c=cfg: rcs.__setitem__(
                h, ElasticAgent(c).run()),
            name=f"agent-{host}", daemon=True)
        t.start()
        threads.append(t)
    for t in threads:
        t.join(timeout=join_timeout)
        assert not t.is_alive(), "gang did not converge in time"
    return rcs


def _read_run(workdir: str):
    from tpunet.utils.logging import MetricsLogger
    run_dir = os.path.join(workdir, "run")
    records = MetricsLogger.read_records(
        os.path.join(run_dir, "metrics.jsonl"))
    with open(os.path.join(run_dir, "run_id")) as f:
        run_id = f.read().strip()
    return records, run_id


def _assert_completed(workdir: str, final_epoch: int = 3) -> list:
    from tpunet.elastic import events
    run_dir = os.path.join(workdir, "run")
    assert events.is_done(run_dir), "no done marker: run never finished"
    records, run_id = _read_run(workdir)
    assert run_id
    for r in records:
        if "run_id" in r:
            assert r["run_id"] == run_id, "stream forked run_ids"
    plain = [r for r in records if "kind" not in r and "epoch" in r]
    assert max(r["epoch"] for r in plain) == final_epoch
    return records


def _assert_crash_report(workdir: str, suffix: str = "") -> None:
    run_dir = os.path.join(workdir, "run")
    pattern = os.path.join(run_dir, "flightrec",
                           f"crash_report{suffix}*")
    reports = glob.glob(pattern)
    assert reports, f"no crash report matching {pattern}"
    with open(reports[0]) as f:
        report = json.load(f)
    for key in ("cause", "events", "stacks", "meta"):
        assert key in report, f"incomplete crash report: missing {key}"
    assert report["events"], "crash report has no ring events"


def _elastic(records, event):
    return [r for r in records
            if r.get("kind") == "obs_elastic" and r["event"] == event]


# -------------------------------------------------------------- legs


def leg_kill_mid_step(workdir: str) -> None:
    """2-process gang; host 1 SIGKILLed mid-epoch; shrink dp 2->1."""
    run_dir = os.path.join(workdir, "run")
    cmd = _train_cmd(
        run_dir, "slow@step=2:delay=2:gen=0;kill@step=3:host=1:gen=0")
    rcs = _run_gang(workdir, cmd, {
        "h0": {"max_restarts": 2},
        "h1": {"max_restarts": 0},
    })
    assert rcs["h0"] == 0 and rcs["h1"] == 2, rcs
    records = _assert_completed(workdir)
    (shrink,) = _elastic(records, "shrink")
    assert shrink["old_world"] == 2 and shrink["new_world"] == 1
    assert _elastic(records, "recovered")[-1]["new_mesh"]["data"] == 1
    _assert_crash_report(workdir, ".p1")


def leg_kill_mid_ckpt(workdir: str) -> None:
    """SIGKILL with the epoch-2 checkpoint write in flight: the torn
    save is skipped, restore comes from the previous intact step."""
    run_dir = os.path.join(workdir, "run")
    cmd = _train_cmd(
        run_dir,
        "kill@ckpt=2:gen=0;slow@step=8:delay=3:steps=4:gen=0")
    rcs = _run_gang(workdir, cmd, {"h0": {"max_restarts": 1}})
    assert rcs["h0"] == 0, rcs
    records = _assert_completed(workdir)
    (restart,) = _elastic(records, "restart")
    assert restart["cause"] == "failed"
    # Restored epoch 1 (the intact save), re-ran epoch 2.
    assert _elastic(records, "recovered")[-1]["epoch"] == 2
    _assert_crash_report(workdir)


def leg_sigterm_grace(workdir: str) -> None:
    """Spot-preemption shape: SIGTERM mid-epoch-2 with a grace
    window; partial save lands inside it; relaunch resumes the same
    epoch and finishes. (Clean exit: no crash report expected.)"""
    run_dir = os.path.join(workdir, "run")
    cmd = _train_cmd(run_dir, "sigterm@step=6:gen=0",
                     extra=["--preempt-grace-s", "30"])
    rcs = _run_gang(workdir, cmd, {"h0": {"max_restarts": 1}})
    assert rcs["h0"] == 0, rcs
    records = _assert_completed(workdir)
    (restart,) = _elastic(records, "restart")
    assert restart["cause"] == "preempted"
    partial = [r for r in records if "kind" not in r
               and r.get("partial")]
    assert partial and partial[0]["epoch"] == 2, \
        "no partial-save row: the grace-window save never landed"


def leg_slow_host_evict(workdir: str) -> None:
    """Proactive checkpoint-and-evict: an injected straggler delay on
    host 1 trips the watchdog's stall detector, the pod checkpoints
    and evicts it, and the survivor re-meshes and finishes."""
    run_dir = os.path.join(workdir, "run")
    cmd = _train_cmd(
        run_dir, "slow@step=10:delay=1.5:steps=6:host=1:gen=0",
        batch=8, synthetic=128,
        extra=["--evict-on-straggler", "--stall-factor", "3",
               "--stall-min-s", "0.2"])
    rcs = _run_gang(workdir, cmd, {
        "h0": {"max_restarts": 2},
        "h1": {"max_restarts": 2},
    }, env_extra={"TPUNET_STOP_POLL_STEPS": "2"})
    # The evicted host leaves CLEANLY (exit 0), the survivor finishes.
    assert rcs["h0"] == 0 and rcs["h1"] == 0, rcs
    records = _assert_completed(workdir)
    # Exactly ONE replica was evicted. Which one is first-claim-wins:
    # in lockstep DP the straggler inflates EVERY replica's step lap,
    # so near-simultaneous watchdog claims are expected
    # (docs/elasticity.md "Proactive checkpoint-and-evict").
    (evict,) = _elastic(records, "evict")
    assert evict["lost"] in (["h0"], ["h1"])
    assert evict["cause"] == "step_stall"
    (shrink,) = _elastic(records, "shrink")
    assert shrink["cause"] == "evict"
    assert shrink["new_world"] == 1
    assert shrink["lost"] == evict["lost"]


LEGS = {
    "kill_mid_step": leg_kill_mid_step,
    "kill_mid_ckpt": leg_kill_mid_ckpt,
    "sigterm_grace": leg_sigterm_grace,
    "slow_host_evict": leg_slow_host_evict,
}


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--legs", default=",".join(LEGS),
                        help="comma-separated subset of: "
                             + ", ".join(LEGS))
    args = parser.parse_args(argv)
    legs = [leg.strip() for leg in args.legs.split(",") if leg.strip()]
    unknown = [leg for leg in legs if leg not in LEGS]
    if unknown:
        print(f"unknown legs: {unknown} (have {sorted(LEGS)})",
              file=sys.stderr)
        return 2
    failed = []
    for leg in legs:
        with tempfile.TemporaryDirectory(
                prefix=f"tpunet-chaos-{leg}-") as workdir:
            print(f"=== chaos leg: {leg}")
            try:
                LEGS[leg](workdir)
                print(f"=== chaos leg: {leg} PASS")
            except Exception as e:  # noqa: BLE001 - report and continue
                print(f"=== chaos leg: {leg} FAIL: {e}",
                      file=sys.stderr)
                failed.append(leg)
    if failed:
        print(f"chaos smoke FAILED: {failed}", file=sys.stderr)
        return 1
    print(f"chaos smoke OK: {len(legs)} leg(s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
