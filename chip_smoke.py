#!/usr/bin/env python
"""Chip smoke: the trainer and the serve engine, end to end, on the TPU.

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --multichip  # four chips: dp=4 training vs dp=1,
                                      # and four one-chip replicas behind
                                      # the router vs one replica alone

The last stdout line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``;
everything else (per-phase JSON, losses, tokens, seconds, cache hits)
is printed on earlier lines. Exit code 0 only when every phase passed
ON A TPU: with no chip, a non-TPU platform, a failed or timed-out
phase, or ``TPUNET_FLASH_INTERPRET`` in the environment, the last
line says ``"ok": false`` and the exit code is non-zero.

This parent process never initialises a jax backend, so it never owns
the chip: every phase is a child process, run one after another, with
``JAX_PLATFORMS=tpu`` in its environment (a missing chip is an error,
never a CPU run). Platform, device kind and device count are what the
children print (train.py's ``JAX devices:`` line, the server's
``listening on`` line).

Phases of the default run, each at the full width of a model the repo
supports (depth is what the model's reference workload uses):

- kernels: on-chip parity (``interpret=False``) of flash_attention
  (plain + segmented), forward and backward, against dense attention
  at T=2048; one width-1 decode step of a GPT-2 XL-wide LM (25 heads
  of 64, 16-token pages, bfloat16 pool) through the
  ``tpunet_paged_decode`` kernel and through the dense gather path,
  logits compared; then the default Trainers' train_steps are lowered:
  the LM step's compiled text must hold the flash custom calls, the
  MobileNetV2 step's no custom call at all (convolutions, BatchNorm
  and ReLU6 are the compiler's).
- latent: the ``latent_lm`` decoder at the widths and the cut of
  ``benchmark/configs/dots3-note-prev.json`` over the serve engine's
  own pool and page table (``model.apply`` as ``Engine._masked_step``
  calls it, returning the logits the engine's step samples from): two
  ``[1, 4096]`` prefill calls, as the engine dispatches them (4,096
  and 2,304 tokens into slots 0 and 7, six slots idle), then 64 width-1 decode steps of both, greedy; every logit row
  kept is compared with the plain reference's full forward over the
  same tokens (``LATENT_LOGIT_TOL``), and the reference's own float8
  control has to fail that tolerance.
- train_vision: ``python train.py`` MobileNetV2 1.0 / 224px / bf16 /
  batch 128 for one short epoch with checkpoints, ``--resume`` for one
  more, ``--eval-only``.
- train_lm: the same CLI, causal LM hidden 2048 / depth 8 / 16 heads /
  T=2048 / batch 8 (403M; depth 12 trains but cannot checkpoint on one
  chip — see LM_TRAIN_WIDTH).
- serve: ``python -m tpunet.serve`` at hidden 2048 / depth 12; requests in
  every prefill bucket, greedy and sampled, one streamed, two
  concurrent; /metrics; SIGTERM and the clean drain.

``--rehearse-cpu`` runs the same phases at tiny sizes with
``JAX_PLATFORMS=cpu`` (``--multichip``: four virtual CPU devices) to
find wrong paths before chip time is spent; a rehearsal never prints
``"ok": true``.

Everything the smoke writes goes under ``--out`` (default
``chiprun_out/smoke`` next to this file) or the compile cache
(``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
FORBIDDEN_ENV = ("TPUNET_FLASH_INTERPRET",)

# LM width of serve and the router replicas: the 604M row of
# runs/bench-lm-mfu/MFU_r03.json (hidden 2048, depth 12, 16 heads).
LM_WIDTH = ["--vit-hidden", "2048", "--vit-depth", "12",
            "--vit-heads", "16"]
# Training takes depth 8 (403M, the other rows of MFU_r03.json) at the
# same width: at depth 12 the run trains and evaluates (7.31 GB of
# state + 8.22 GB of step temporaries of 16.91 GB) and then dies in the
# epoch's checkpoint, whose on-device snapshot (tpunet/ckpt/orbax_io.py
# _snapshot) needs a second copy of the state — RESOURCE_EXHAUSTED,
# "allocate 64.00M ... 9.26M free" (my chip run, PR 21). The CLI has no
# flag that switches checkpoints off.
LM_TRAIN_WIDTH = ["--vit-hidden", "2048", "--vit-depth", "8",
                  "--vit-heads", "16"]
TINY_LM_WIDTH = ["--vit-hidden", "64", "--vit-depth", "2",
                 "--vit-heads", "4"]
# The paged decode check: the serve cell's geometry (benchmark/configs/
# gpt2-xl.json, slots 16, 16-token pages) at the train cell's depth.
# How far the two paths may differ in a logit. Both are sound, and
# both round to bfloat16 at every layer: on the chip they differ by
# 0.0316 where the largest logit is 4.28 — one bfloat16 step there is
# 0.03125 (my chip run, PR 26). That is the size of the 0.023-0.039 by
# which a served token's float32 reference logit lies below the best
# one when the served path is sound, and half the 0.12 at which the
# benchmark calls it unsound (benchmark/workloads/
# gpt2-xl.serve-closed16.json, served_logit_gap_max); a kernel that
# drops a page or a mask is off by tenths.
PAGED_DECODE_LOGIT_TOL = 0.06
# latent phase: largest |program logit - float32 reference logit| over
# the compared rows (the last prefill position, three positions inside
# the prefill on either side of index_topk, 64 decode steps), per row of
# two. Readings on the chip (PERF.md section 6, PR 27; logits reach
# +-7.2): the program 0.353 and 0.300; the reference with every
# product's operands and results rounded to float8 5.02 and 3.41, which
# has to fail. With the indexer's inputs in bfloat16 the program itself
# read 1.95-2.85: the tolerance is also what holds the indexer's path in
# float32.
LATENT_LOGIT_TOL = 1.0

_DEVICES_RE = re.compile(
    r"JAX devices: (\d+) \((\d+) local\), processes: (\d+), "
    r"platform: (\w+), device_kind: (.+)")
_LISTEN_RE = re.compile(
    r"listening on http://[^ ]+ \(.*platform=(\w+), "
    r"device_kind=(.+?), visible_chips=([\w,]+)\)")
_COMPILE_RE = re.compile(
    r"Compile: (\d+) programs, ([\d.]+)s, (\d+) from cache")
_EPOCH_RE = re.compile(
    r"Epoch (\d+)/(\d+) Time: ([\d.]+)s Train Loss: (\S+) "
    r"Train Acc: (\S+) Test Loss: (\S+) Test Acc: (\S+)")


class PhaseError(Exception):
    pass


def say(obj) -> None:
    print(json.dumps(obj) if not isinstance(obj, str) else obj,
          flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise PhaseError(msg)


# --------------------------------------------------------------------
# Children
# --------------------------------------------------------------------

class Ctx:
    def __init__(self, args):
        self.rehearse = args.rehearse_cpu
        self.out = os.path.abspath(args.out)
        self.multichip = args.multichip
        self.devices: list = []   # (platform, kind, count) per child
        self.t0 = time.monotonic()
        self.compile_seconds = 0.0   # summed over the children
        self.cache_hits = 0

    def child_env(self) -> dict:
        env = dict(os.environ)
        if self.rehearse:
            env["JAX_PLATFORMS"] = "cpu"
            if self.multichip:
                env["XLA_FLAGS"] = (
                    env.get("XLA_FLAGS", "")
                    + " --xla_force_host_platform_device_count=4").strip()
        else:
            env["JAX_PLATFORMS"] = "tpu"
        env["PYTHONUNBUFFERED"] = "1"
        return env

    def note_device(self, platform: str, kind: str, count: int) -> None:
        self.devices.append((platform, kind.strip(), int(count)))
        if not self.rehearse:
            check(platform == "tpu",
                  f"child ran on platform {platform!r}, not tpu")


def _kill_group(proc: subprocess.Popen) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        pass


def run_child(ctx: Ctx, name: str, argv: list, timeout: float) -> str:
    """Run one child to its end; returns its combined output (also
    kept as ``<out>/<name>.log``). Raises PhaseError on a non-zero
    exit or a timeout (the child's process group is killed)."""
    log_path = os.path.join(ctx.out, f"{name}.log")
    t0 = time.monotonic()
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(
            argv, cwd=HERE, env=ctx.child_env(), stdout=log,
            stderr=subprocess.STDOUT, start_new_session=True)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            _kill_group(proc)
            raise PhaseError(f"{name}: timed out after {timeout:.0f}s "
                             f"(log: {log_path})")
        finally:
            _kill_group(proc)
    with open(log_path, errors="replace") as f:
        text = f.read()
    secs = time.monotonic() - t0
    stats = _compile_stats(text)
    ctx.compile_seconds += stats.get("compile_seconds", 0.0)
    ctx.cache_hits += stats.get("cache_hits", 0)
    say({"child": name, "rc": rc, "seconds": round(secs, 1), **stats})
    if rc != 0:
        tail = "\n".join(text.splitlines()[-25:])
        raise PhaseError(f"{name}: exit code {rc}\n{tail}")
    return text


def _compile_stats(text: str) -> dict:
    m = None
    for m in _COMPILE_RE.finditer(text):
        pass
    if m is None:
        return {}
    return {"compile_programs": int(m.group(1)),
            "compile_seconds": float(m.group(2)),
            "cache_hits": int(m.group(3))}


def _trainer_device(ctx: Ctx, name: str, text: str) -> dict:
    m = _DEVICES_RE.search(text)
    check(m is not None, f"{name}: no 'JAX devices:' line")
    ctx.note_device(m.group(4), m.group(5), int(m.group(1)))
    return {"platform": m.group(4), "kind": m.group(5).strip(),
            "count": int(m.group(1))}


def _epochs(text: str) -> list:
    return [{"epoch": int(m.group(1)), "seconds": float(m.group(3)),
             "train_loss": float(m.group(4)),
             "train_acc": float(m.group(5)),
             "test_loss": float(m.group(6)),
             "test_acc": float(m.group(7))}
            for m in _EPOCH_RE.finditer(text)]


def _finite(*vals) -> bool:
    return all(math.isfinite(v) for v in vals)


def _peak_bytes(ckpt_dir: str):
    """Largest device peak the run's own obs_epoch records report
    (None when the backend reported none)."""
    path = os.path.join(ckpt_dir, "metrics.jsonl")
    peak = None
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                for dev in rec.get("device_memory") or ():
                    val = dev.get("peak_bytes_in_use")
                    if isinstance(val, (int, float)):
                        peak = max(peak or 0, int(val))
    return peak


def _drop_checkpoints(ckpt_dir: str) -> None:
    """Keep logs and metrics, drop the bulky orbax trees (the output
    directory travels back from the chip machine)."""
    for sub in ("state", "best", "last"):
        shutil.rmtree(os.path.join(ckpt_dir, sub), ignore_errors=True)


# --------------------------------------------------------------------
# Phase: kernels (in-process child — see _kernels_child)
# --------------------------------------------------------------------

def phase_kernels(ctx: Ctx) -> dict:
    argv = [sys.executable, os.path.abspath(__file__), "--phase",
            "kernels"]
    if ctx.rehearse:
        argv.append("--rehearse-cpu")
    text = run_child(ctx, "kernels", argv, timeout=900)
    result = None
    for line in text.splitlines():
        if line.startswith("{"):
            say(line)
            rec = json.loads(line)
            if rec.get("phase") == "kernels":
                result = rec
    check(result is not None, "kernels: no result record")
    dev = result["device"]
    ctx.note_device(dev["platform"], dev["kind"], dev["count"])
    check(result["ok"], f"kernels: {result.get('failures')}")
    return result


def phase_latent(ctx: Ctx) -> dict:
    argv = [sys.executable, os.path.abspath(__file__), "--phase", "latent"]
    if ctx.rehearse:
        argv.append("--rehearse-cpu")
    text = run_child(ctx, "latent", argv, timeout=1500)
    result = None
    for line in text.splitlines():
        if line.startswith("{"):
            say(line)
            rec = json.loads(line)
            if rec.get("phase") == "latent":
                result = rec
    check(result is not None, "latent: no result record")
    dev = result["device"]
    ctx.note_device(dev["platform"], dev["kind"], dev["count"])
    check(result["ok"], f"latent: {result.get('failures')}")
    return result


def _rebuild_native_loader() -> None:
    """The native loader that runs is built from the committed source:
    drop whatever binary rode along in the working tree (git ignores
    tpunet/data/_lib/) before the first training child, which rebuilds
    it from cxx/batcher.cc, or prints 'Host loader: numpy' where there
    is no compiler."""
    shutil.rmtree(os.path.join(HERE, "tpunet", "data", "_lib"),
                  ignore_errors=True)


# --------------------------------------------------------------------
# Phase: train_vision
# --------------------------------------------------------------------

def phase_train_vision(ctx: Ctx) -> dict:
    _rebuild_native_loader()
    ckpt = os.path.join(ctx.out, "vision")
    shutil.rmtree(ckpt, ignore_errors=True)
    base = [sys.executable, os.path.join(HERE, "train.py"),
            "--preset", "single", "--mesh-data", "1",
            "--dataset", "synthetic", "--checkpoint-dir", ckpt]
    if ctx.rehearse:
        base += ["--synthetic-size", "64", "--batch-size", "16",
                 "--image-size", "32", "--width-mult", "0.5"]
    else:
        # Defaults define the reference workload: MobileNetV2 1.0,
        # 224px, bf16, batch 128.
        base += ["--synthetic-size", "512"]
    first = run_child(ctx, "train_vision", base + ["--epochs", "1"],
                      timeout=600)
    dev = _trainer_device(ctx, "train_vision", first)
    loader = re.search(r"Host loader: (.+)", first)
    check(loader is not None, "train_vision: no 'Host loader:' line")
    ep1 = _epochs(first)
    check(len(ep1) == 1, f"train_vision: {len(ep1)} epoch lines")
    check(_finite(ep1[0]["train_loss"], ep1[0]["test_loss"]),
          f"train_vision: non-finite losses {ep1}")
    check(os.path.isdir(os.path.join(ckpt, "state"))
          and os.path.isdir(os.path.join(ckpt, "best")),
          "train_vision: best/last checkpoints missing")

    second = run_child(ctx, "train_vision_resume",
                       base + ["--epochs", "2", "--resume"], timeout=600)
    _trainer_device(ctx, "train_vision_resume", second)
    check("Resumed from epoch 1" in second,
          "train_vision_resume: no 'Resumed from epoch 1' line")
    ep2 = _epochs(second)
    check([e["epoch"] for e in ep2] == [2],
          f"train_vision_resume: ran epochs {[e['epoch'] for e in ep2]}"
          ", expected [2]")
    check(_finite(ep2[0]["train_loss"], ep2[0]["test_loss"]),
          f"train_vision_resume: non-finite losses {ep2}")

    third = run_child(ctx, "train_vision_eval",
                      base + ["--eval-only"], timeout=600)
    _trainer_device(ctx, "train_vision_eval", third)
    ev = re.search(r"Eval: Test Loss: (\S+) Test Acc: (\S+)", third)
    check(ev is not None, "train_vision_eval: no 'Eval:' line")
    check(_finite(float(ev.group(1))),
          f"train_vision_eval: non-finite loss {ev.group(1)}")
    result = {"phase": "train_vision", "device": dev,
              "host_loader": loader.group(1).strip(),
              "epoch1": ep1[0], "epoch2": ep2[0],
              "eval_only": {"test_loss": float(ev.group(1)),
                            "test_acc": float(ev.group(2))},
              "peak_bytes": _peak_bytes(ckpt)}
    _drop_checkpoints(ckpt)
    return result


# --------------------------------------------------------------------
# Phase: train_lm
# --------------------------------------------------------------------

def phase_train_lm(ctx: Ctx) -> dict:
    ckpt = os.path.join(ctx.out, "lm")
    shutil.rmtree(ckpt, ignore_errors=True)
    argv = [sys.executable, os.path.join(HERE, "train.py"),
            "--preset", "single", "--mesh-data", "1",
            "--dataset", "synthetic_lm", "--model", "lm",
            "--checkpoint-dir", ckpt, "--epochs", "1"]
    if ctx.rehearse:
        argv += TINY_LM_WIDTH + ["--seq-len", "64", "--batch-size", "4",
                                 "--synthetic-size", "16"]
    else:
        argv += LM_TRAIN_WIDTH + ["--seq-len", "2048", "--batch-size",
                                  "8", "--synthetic-size", "32"]
    try:
        text = run_child(ctx, "train_lm", argv, timeout=900)
        dev = _trainer_device(ctx, "train_lm", text)
        ep = _epochs(text)
        check(len(ep) == 1, f"train_lm: {len(ep)} epoch lines")
        check(_finite(ep[0]["train_loss"], ep[0]["test_loss"]),
              f"train_lm: non-finite losses {ep}")
        return {"phase": "train_lm", "device": dev, "epoch1": ep[0],
                "peak_bytes": _peak_bytes(ckpt)}
    finally:
        _drop_checkpoints(ckpt)


# --------------------------------------------------------------------
# Phase: serve (and the helpers the router path shares)
# --------------------------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(method: str, url: str, body=None, timeout: float = 120.0):
    """-> (status, parsed JSON or raw text)."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            status, raw = resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        status, raw = e.code, e.read().decode()
    try:
        return status, json.loads(raw)
    except ValueError:
        return status, raw


def _prompt(n: int, seed: int, vocab: int = 256) -> list:
    """n deterministic tokens (LCG from the seed; no numpy here)."""
    out, x = [], seed * 2654435761 % 2**32 or 1
    for _ in range(n):
        x = (1103515245 * x + 12345) % 2**31
        out.append(x % vocab)
    return out


def _generate(base: str, body: dict, timeout: float = 300.0) -> dict:
    """POST /v1/generate; the answer must be a non-error finish with
    a non-empty token list."""
    status, out = _http("POST", base + "/v1/generate", body, timeout)
    check(status == 200 and isinstance(out, dict),
          f"generate: HTTP {status}: {str(out)[:300]}")
    check(out.get("finish_reason") in ("length", "stop"),
          f"generate: finish_reason {out.get('finish_reason')!r} "
          f"error {out.get('error')!r}")
    check(out.get("tokens"), f"generate: empty token list: {out}")
    check(not out.get("error"), f"generate: error {out.get('error')!r}")
    return out


def _generate_stream(base: str, body: dict,
                     timeout: float = 300.0) -> dict:
    req = urllib.request.Request(
        base + "/v1/generate",
        data=json.dumps(dict(body, stream=True)).encode(),
        method="POST")
    toks, done = [], None
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        check(resp.status == 200, f"stream: HTTP {resp.status}")
        for raw in resp:
            raw = raw.strip()
            if not raw:
                continue
            frame = json.loads(raw)
            if frame.get("done"):
                done = frame
                break
            if "token" in frame:
                toks.append(frame["token"])
    check(done is not None, "stream: no done frame")
    check(done.get("finish_reason") in ("length", "stop"),
          f"stream: finish_reason {done.get('finish_reason')!r} "
          f"error {done.get('error')!r}")
    check(toks, "stream: no token frames")
    return {"tokens": toks, "finish_reason": done["finish_reason"]}


class Server:
    """One ``python -m tpunet.serve`` (or ``tpunet.router``) child."""

    def __init__(self, ctx: Ctx, name: str, argv: list, port: int):
        self.ctx, self.name, self.port = ctx, name, port
        self.base = f"http://127.0.0.1:{port}"
        self.log_path = os.path.join(ctx.out, f"{name}.log")
        self._log = open(self.log_path, "wb")
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(
            argv, cwd=HERE, env=ctx.child_env(), stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True)

    def text(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def wait_healthy(self, timeout: float, path: str = "/healthz",
                     ready=lambda status, out: status == 200) -> float:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise PhaseError(
                    f"{self.name}: exited {self.proc.returncode} "
                    "before it was ready\n"
                    + "\n".join(self.text().splitlines()[-25:]))
            try:
                status, out = _http("GET", self.base + path, timeout=5)
                if ready(status, out):
                    return time.monotonic() - self.t0
            except (OSError, ValueError):
                pass
            time.sleep(0.5)
        raise PhaseError(f"{self.name}: not ready after {timeout:.0f}s"
                         "\n" + "\n".join(self.text().splitlines()[-25:]))

    def terminate(self, timeout: float = 90.0) -> int:
        """SIGTERM and wait for the drain; returns the exit code."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise PhaseError(f"{self.name}: no exit {timeout:.0f}s "
                             "after SIGTERM")

    def close(self) -> None:
        _kill_group(self.proc)
        self._log.close()


def _serve_argv(ctx: Ctx) -> list:
    """The model/pool flags every serve child shares."""
    if ctx.rehearse:
        return (["--checkpoint-dir", ""] + TINY_LM_WIDTH
                + ["--max-seq-len", "128", "--prefill-buckets", "8,32",
                   "--emit-every-s", "2"])
    # Paged KV, device sampling and the prefill buckets (32/128/512)
    # stay at their defaults; random weights from the default seed.
    return (["--checkpoint-dir", ""] + LM_WIDTH
            + ["--emit-every-s", "2"])


def _bucket_prompts(ctx: Ctx) -> list:
    return [5, 20] if ctx.rehearse else [20, 100, 400]


def phase_serve(ctx: Ctx) -> dict:
    mdir = os.path.join(ctx.out, "serve")
    shutil.rmtree(mdir, ignore_errors=True)
    port = _free_port()
    server = Server(ctx, "serve", [
        sys.executable, "-m", "tpunet.serve", "--port", str(port),
        "--metrics-dir", mdir] + _serve_argv(ctx), port)
    try:
        ready_s = server.wait_healthy(600)
        m = _LISTEN_RE.search(server.text())
        check(m is not None, "serve: no device on the 'listening' line")
        ctx.note_device(m.group(1), m.group(2), 1)
        new = 6 if ctx.rehearse else 16
        answers = []
        # One request per prefill bucket, greedy.
        for i, n in enumerate(_bucket_prompts(ctx)):
            out = _generate(server.base, {
                "tokens": _prompt(n, i + 1), "max_new_tokens": new})
            check(len(out["tokens"]) == new,
                  f"serve: {len(out['tokens'])} tokens, wanted {new}")
            answers.append({"prompt_len": n, "tokens": out["tokens"],
                            "finish_reason": out["finish_reason"],
                            "ttft_ms": out.get("ttft_ms")})
        # Two identical greedy requests, concurrent: same tokens.
        body = {"tokens": _prompt(_bucket_prompts(ctx)[0], 7),
                "max_new_tokens": new}
        pair: list = [None, None]

        def fire(i):
            try:
                pair[i] = _generate(server.base, body)
            except Exception as e:  # noqa: BLE001 — joined below
                pair[i] = e
        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        for got in pair:
            check(isinstance(got, dict),
                  f"serve: concurrent request failed: {got}")
        check(pair[0]["tokens"] == pair[1]["tokens"],
              f"serve: identical greedy requests differ: "
              f"{pair[0]['tokens']} vs {pair[1]['tokens']}")
        # Sampled, and sampled + streamed with the same seed.
        samp = dict(body, temperature=0.8, top_k=40, top_p=0.95,
                    seed=11)
        sampled = _generate(server.base, samp)
        streamed = _generate_stream(server.base, samp)
        check(streamed["tokens"] == sampled["tokens"],
              f"serve: streamed sample {streamed['tokens']} differs "
              f"from unary {sampled['tokens']} at the same seed")
        status, metrics = _http("GET", server.base + "/metrics")
        check(status == 200 and isinstance(metrics, dict)
              and any(k.startswith("serve_") for k in metrics),
              f"serve: /metrics HTTP {status} without serve_* keys")
        rc = server.terminate()
        text = server.text()
        check("draining" in text and "drained (clean)" in text,
              "serve: no 'draining ... drained (clean)' exit")
        check(rc == 0, f"serve: exit code {rc} after drain")
        with open(os.path.join(mdir, "metrics.jsonl")) as f:
            last = json.loads(f.read().strip().splitlines()[-1])
        stats = _compile_stats(text)
        ctx.compile_seconds += stats.get("compile_seconds", 0.0)
        ctx.cache_hits += stats.get("cache_hits", 0)
        check(last.get("kind") == "obs_serve" and last.get("final")
              is True, f"serve: last metrics record is {last.get('kind')}"
              f" final={last.get('final')}")
        return {"phase": "serve",
                "device": {"platform": m.group(1),
                           "kind": m.group(2).strip(), "count": 1},
                "ready_seconds": round(ready_s, 1),
                "seconds": round(time.monotonic() - server.t0, 1),
                **stats,
                "answers": answers,
                "greedy_pair": pair[0]["tokens"],
                "sampled": sampled["tokens"],
                "requests_total": metrics.get("serve_requests_total"),
                # The server exports no device-memory gauge; what it
                # does say is the size of its KV pool.
                "kv_bytes_per_token":
                    metrics.get("serve_kv_bytes_per_token"),
                "kv_pages_total": metrics.get("serve_kv_pages_total")}
    finally:
        server.close()


# --------------------------------------------------------------------
# --multichip paths
# --------------------------------------------------------------------

def phase_dp(ctx: Ctx) -> dict:
    """Data-parallel training on the data=4 mesh against data=1 at the
    same global batch and seed (tests/test_train.py
    test_metrics_identical_across_mesh_sizes, on the chip)."""
    _rebuild_native_loader()
    runs = {}
    for label, mesh in (("dp1", ["--preset", "single",
                                 "--mesh-data", "1"]),
                        ("dp4", ["--preset", "distributed"])):
        ckpt = os.path.join(ctx.out, label)
        shutil.rmtree(ckpt, ignore_errors=True)
        argv = [sys.executable, os.path.abspath(__file__), "--phase",
                "dp", "--"] + mesh + [
            "--dataset", "synthetic", "--epochs", "1",
            "--checkpoint-dir", ckpt]
        if ctx.rehearse:
            argv += ["--synthetic-size", "64", "--batch-size", "16",
                     "--image-size", "32", "--width-mult", "0.5",
                     "--dtype", "float32"]
        else:
            argv += ["--synthetic-size", "2048", "--batch-size", "512"]
        try:
            text = run_child(ctx, label, argv, timeout=900)
        finally:
            _drop_checkpoints(ckpt)
        rec = None
        for line in text.splitlines():
            if line.startswith("{"):
                say(line)
                rec = json.loads(line)
        check(rec is not None and rec.get("phase") == "dp",
              f"{label}: no result record")
        dev = rec["device"]
        ctx.note_device(dev["platform"], dev["kind"], dev["count"])
        runs[label] = rec
    a, b = runs["dp1"], runs["dp4"]
    check(a["mesh"]["data"] == 1 and b["mesh"]["data"] == 4,
          f"dp: meshes {a['mesh']} / {b['mesh']}")
    check(a["global_batch"] == b["global_batch"],
          f"dp: global batches {a['global_batch']} / "
          f"{b['global_batch']}")
    # test_metrics_identical_across_mesh_sizes: eval rtol 1e-4 in
    # float32, train rtol 2e-2; the bf16 eval bound is
    # DP_EVAL_RTOL_BF16 below.
    eval_rtol = 1e-4 if ctx.rehearse else DP_EVAL_RTOL_BF16
    rel_eval = abs(a["eval_loss"] - b["eval_loss"]) / abs(a["eval_loss"])
    rel_train = (abs(a["train_loss"] - b["train_loss"])
                 / abs(a["train_loss"]))
    say({"dp_compare": {"eval_loss": [a["eval_loss"], b["eval_loss"]],
                        "eval_rel": rel_eval, "eval_rtol": eval_rtol,
                        "train_loss": [a["train_loss"],
                                       b["train_loss"]],
                        "train_rel": rel_train, "train_rtol": 2e-2}})
    check(a["eval_count"] == b["eval_count"],
          f"dp: eval counts {a['eval_count']} / {b['eval_count']}")
    check(rel_eval <= eval_rtol,
          f"dp: initial eval loss differs by {rel_eval:.3g} "
          f"(> {eval_rtol})")
    check(rel_train <= 2e-2,
          f"dp: train loss differs by {rel_train:.3g} (> 2e-2)")
    used = [d for d in b["devices"] if d["bytes_in_use"] > 0]
    check(len(b["devices"]) == 4 and len(used) == 4,
          f"dp4: devices holding bytes: {b['devices']}")
    check(len(set(b["batch_sharding_devices"])) == 4,
          f"dp4: batch on devices {b['batch_sharding_devices']}")
    check(len([d for d in a["devices"] if d["bytes_in_use"] > 0]) == 1,
          f"dp1: devices holding bytes: {a['devices']}")
    return {"phase": "dp", "eval_rel": rel_eval,
            "train_rel": rel_train,
            "dp4_device_bytes": [d["bytes_in_use"] for d in b["devices"]],
            "dp4_device_peak_bytes": [d["peak_bytes_in_use"]
                                      for d in b["devices"]],
            "dp1_device_bytes": [d["bytes_in_use"] for d in a["devices"]],
            "batch_sharding": b["batch_sharding"],
            "param_sharding": b["param_sharding"]}


# Widened bf16 bound for dp=4 vs dp=1 initial eval loss (float32:
# 1e-4). NOT yet measured: no four-chip host came free in PR 21, so
# this is an estimate (bf16 keeps 8 bits of mantissa; the reduction
# order of a 512-row batch changes with the mesh). The first four-chip
# run prints the measured eval_rel — set the bound from it and write
# it down in CHANGES.md.
DP_EVAL_RTOL_BF16 = 2e-3


def phase_router(ctx: Ctx) -> dict:
    """Four one-chip replicas behind the router, against one replica
    alone (run first, on one chip)."""
    new = 6 if ctx.rehearse else 16
    body = {"tokens": _prompt(_bucket_prompts(ctx)[0], 7),
            "max_new_tokens": new}
    # 1. The comparison: one serve child, alone.
    port = _free_port()
    solo = Server(ctx, "solo", [
        sys.executable, "-m", "tpunet.serve", "--port", str(port),
        "--metrics-dir", os.path.join(ctx.out, "solo")]
        + _serve_argv(ctx), port)
    try:
        solo.wait_healthy(600)
        m = _LISTEN_RE.search(solo.text())
        check(m is not None, "solo: no device on the 'listening' line")
        ctx.note_device(m.group(1), m.group(2), 1)
        want = _generate(solo.base, body)["tokens"]
        check(solo.terminate() == 0, "solo: non-zero exit after drain")
    finally:
        solo.close()
    say({"solo_tokens": want})

    # 2. The fleet.
    rdir = os.path.join(ctx.out, "router")
    shutil.rmtree(rdir, ignore_errors=True)
    aot = os.path.join(ctx.out, "aot")
    shutil.rmtree(aot, ignore_errors=True)
    port = _free_port()
    router = Server(ctx, "router", [
        sys.executable, "-m", "tpunet.router", "--spawn", "4",
        "--port", str(port), "--metrics-dir", rdir, "--aot-cache", aot,
        "--boot-timeout-s", "900", "--probe-interval-s", "1",
        "--"] + _serve_argv(ctx), port)
    try:
        def four_healthy(status, out):
            reps = out.get("replicas", out) if isinstance(out, dict) \
                else out
            states = [r.get("state") for r in reps] \
                if isinstance(reps, list) else []
            return status == 200 and states.count("healthy") >= 4
        ready_s = router.wait_healthy(1200, "/replicas", four_healthy)
        # Each replica's log names the chip it was given.
        chips = {}
        for i in range(4):
            with open(os.path.join(rdir, f"replica-{i}.log"),
                      errors="replace") as f:
                lm = _LISTEN_RE.search(f.read())
            check(lm is not None, f"replica-{i}: no 'listening' line")
            ctx.note_device(lm.group(1), lm.group(2), 1)
            chips[i] = lm.group(3)
        check(len(set(chips.values())) == 4,
              f"router: replicas share chips: {chips}")
        # The same greedy prompt until every replica has served it.
        # (A session key steers the rendezvous hash; /replicas says
        # who has been routed to.)
        served: dict = {}
        for attempt in range(64):
            out = _generate(router.base, dict(
                body, session=f"smoke-{attempt}"))
            check(out["tokens"] == want,
                  f"router: request {attempt} answered {out['tokens']}"
                  f", one replica alone {want}")
            _, view = _http("GET", router.base + "/replicas")
            served = {r["name"]: r["requests_routed"]
                      for r in view["replicas"]}
            if sum(1 for n in served.values() if n > 0) >= 4:
                break
        check(sum(1 for n in served.values() if n > 0) >= 4,
              f"router: requests routed per replica after 64: {served}")
        check(router.terminate(180) == 0,
              "router: non-zero exit after drain")
    finally:
        router.close()

    # 3. A second boot of one replica from the AOT store.
    port = _free_port()
    again = Server(ctx, "aot_reboot", [
        sys.executable, "-m", "tpunet.serve", "--port", str(port),
        "--metrics-dir", os.path.join(ctx.out, "aot_reboot"),
        "--aot-cache", aot] + _serve_argv(ctx), port)
    try:
        reboot_s = again.wait_healthy(600)
        text = again.text()
        status_line = re.search(r"aot warm-start: (\{.*\})", text)
        check(status_line is not None,
              "aot_reboot: no 'aot warm-start:' line")
        check("'loaded'" in status_line.group(1)
              and "compiled" not in status_line.group(1),
              f"aot_reboot: programs not all loaded: "
              f"{status_line.group(1)}")
        got = _generate(again.base, body)["tokens"]
        check(got == want, f"aot_reboot: answered {got}, wanted {want}")
        check(again.terminate() == 0,
              "aot_reboot: non-zero exit after drain")
    finally:
        again.close()
    shutil.rmtree(aot, ignore_errors=True)
    return {"phase": "router", "fleet_ready_seconds": round(ready_s, 1),
            "replica_chips": chips, "served": served,
            "aot_reboot_ready_seconds": round(reboot_s, 1),
            "aot_status": status_line.group(1), "tokens": want}


# --------------------------------------------------------------------
# In-process children (these import jax; the parent never gets here)
# --------------------------------------------------------------------

def _device_record() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _paged_decode_check(rehearse: bool, rows: list,
                        failures: list) -> None:
    """One width-1 decode step over one pool, through the in-place
    kernel and through the dense gather path (the dispatch's view of
    the backend is replaced here, in the smoke; the program has no
    switch): the pool is filled by a 128-wide prefill, which takes the
    dense path either way, then ragged rows decode one token."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from tpunet.config import ModelConfig
    from tpunet.models import create_model, init_variables
    from tpunet.models.vit import PagedKV
    from tpunet.ops import paged_decode

    if rehearse:
        hidden, depth, heads, vocab, max_len, fill = 320, 2, 5, 64, 64, 32
    else:
        hidden, depth, heads, vocab, max_len, fill = (1600, 12, 25,
                                                      50257, 1024, 128)
    slots, pt = 16, 16
    pps = max_len // pt
    model = create_model(ModelConfig(
        name="lm", vit_hidden=hidden, vit_depth=depth, vit_heads=heads,
        vocab_size=vocab, max_seq_len=max_len, dropout_rate=0.0,
        dtype="bfloat16", param_dtype="float32"))
    params = init_variables(model, jax.random.PRNGKey(3),
                            seq_len=8)["params"]
    paged = PagedKV(pages=slots * pps + 1, page_tokens=pt)
    rng = np.random.default_rng(9)
    table = jnp.asarray(rng.permutation(
        np.arange(1, slots * pps + 1)).reshape(slots, pps), jnp.int32)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((slots, max_len), jnp.int32),
        decode=True, paged_kv=paged, page_table=table))
    cache = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, a.dtype), shapes["cache"])

    def step(params, cache, tokens, positions, active):
        logits, mutated = model.apply(
            {"params": params, "cache": cache}, tokens, train=False,
            decode=True, pos_offset=positions, decode_active=active,
            paged_kv=paged, page_table=table, mutable=["cache"])
        return mutated["cache"], logits

    def tokens(width):
        return jnp.asarray(rng.integers(0, vocab, size=(slots, width)),
                           jnp.int32)

    everyone = jnp.ones((slots,), bool)
    cache, _ = jax.jit(step)(params, cache, tokens(fill),
                             jnp.zeros((slots,), jnp.int32), everyone)
    # a page boundary and its neighbours, one key, the whole prefill;
    # every fourth row inactive
    positions = jnp.asarray(
        [fill, fill - 1, pt, pt - 1, pt + 1, 1, 0, fill // 2] * 2,
        jnp.int32)
    active = jnp.asarray([i % 4 != 3 for i in range(slots)])
    tok = tokens(1)
    real = paged_decode._on_tpu
    out = {}
    try:
        for path, on in (("kernel", True), ("dense", False)):
            paged_decode._on_tpu = lambda on=on: on
            # a function of its own: jit's trace cache is keyed by it
            fn = jax.jit(lambda *a: step(*a))
            text = fn.lower(params, cache, tok, positions,
                            active).as_text()
            calls = text.count("tpunet_paged_decode")
            if bool(calls) != on and not rehearse:   # interpreted: none
                failures.append({"paged_decode": f"{path} path lowered "
                                 f"with {calls} kernel mentions"})
            out[path] = np.asarray(
                fn(params, cache, tok, positions, active)[1],
                np.float32)[np.asarray(active), 0]
    finally:
        paged_decode._on_tpu = real
    err = np.abs(out["kernel"] - out["dense"])
    row = {"kernel": f"paged_decode[{slots}x1 h{heads}x"
                     f"{hidden // heads} depth{depth} pt{pt} bf16 pool]",
           "tensor": "logits", "max_abs_err": float(err.max()),
           "mean_abs_err": float(err.mean()),
           "max_abs_ref": float(np.abs(out["dense"]).max()),
           "argmax_agree": float(np.mean(
               out["kernel"].argmax(-1) == out["dense"].argmax(-1))),
           "atol": PAGED_DECODE_LOGIT_TOL}
    rows.append(row)
    say(row)
    if not (np.isfinite(out["kernel"]).all()
            and err.max() <= PAGED_DECODE_LOGIT_TOL):
        failures.append(row)


# the latent phase's rehearsal sizes (tests/ share them: every layer kind,
# index_topk and the window shorter than the sequences)
LATENT_TINY = dict(
    hidden_size=64, intermediate_size=96, num_attention_heads=4,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, index_n_heads=4, index_head_dim=16,
    index_topk=6, swa_num_attention_heads=2, swa_q_lora_rank=32,
    swa_kv_lora_rank=24, swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8,
    swa_v_head_dim=16, sliding_window_size=5, num_experts_per_tok=2,
    moe_intermediate_size=32)


def _latent_child(rehearse: bool) -> int:
    """The ``latent`` phase (see the module's text). The program goes
    first; its parameters, pool and engine are dropped before the
    reference makes its own copy of the weights (the two do not fit the
    chip together)."""
    import gc

    import numpy as np

    import jax
    import jax.numpy as jnp

    from benchmark import harness, weights
    from tpunet.config import ModelConfig, ServeConfig
    from tpunet.models import create_model
    from tpunet.serve.engine import Engine
    from tpunet.utils.cache import (compile_stats_line,
                                    enable_persistent_compile_cache)

    t0 = time.monotonic()
    enable_persistent_compile_cache()
    cfg = harness.load_json("benchmark", "configs", "dots3-note-prev.json")
    ref = harness.load_module(
        os.path.join(HERE, "benchmark", "reference", "dots3-note-prev.py"),
        "smoke_reference_dots3")
    slots, bucket, lens, steps, seed = 8, 4096, (4096, 2304), 64, 1618033989
    if rehearse:
        cfg.update(LATENT_TINY, n_routed_experts=4, held_experts=[0, 1, 2, 3],
                   n_routed_experts_published=8, vocab_size=64,
                   param_dtype="float32")
        cfg["program"]["model"].update(vocab_size=64, max_seq_len=64,
                                       dtype="float32", param_dtype="float32")
        cfg["program"]["model"]["latent"].update(
            LATENT_TINY, n_routed_experts=8, held_experts=[0, 1, 2, 3])
        slots, bucket, lens, steps = 3, 24, (24, 13), 6
    model_kw = cfg["program"]["model"]
    rows_at = (0, slots - 1)                  # the slots that run
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
               for n in lens]
    topk = cfg["index_topk"]
    inside = [[p for p in (topk - 1, topk, (topk + n) // 2) if p < n - 1]
              for n in lens]                  # prefill positions kept

    model = create_model(ModelConfig(**model_kw))
    params = weights.make_tree(ref.param_spec(cfg, "serve"), seed,
                               dtype=model_kw["param_dtype"])
    engine = Engine(model, {"params": params}, ServeConfig(
        slots=slots, prefill_buckets=(bucket,), queue_max=8,
        emit_every_s=0.0))
    paged = engine._paged_kv

    def step(params, cache, tokens, positions, active, page_table):
        logits, mutated = model.apply(
            {"params": params, "cache": cache}, tokens, train=False,
            decode=True, pos_offset=positions, decode_active=active,
            paged_kv=paged, page_table=page_table, mutable=["cache"])
        return mutated["cache"], logits

    step = jax.jit(step, donate_argnums=(1,))
    active = np.zeros((slots,), bool)
    got = [{} for _ in lens]                  # position -> logits row
    for i, (slot, prompt) in enumerate(zip(rows_at, prompts)):
        check(engine._alloc_pages_for(slot, len(prompt) + steps)
              is not None, "latent: no pages")
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :len(prompt)] = prompt
        active[slot] = True
        engine._cache, logits = step(
            params, engine._cache, toks, np.zeros((1,), np.int32),
            np.ones((1,), bool), engine._page_table[slot:slot + 1])
        for p in inside[i] + [len(prompt) - 1]:
            got[i][p] = np.asarray(logits[0, p], np.float32)
    del logits
    prefill_s = time.monotonic() - t0
    seqs = [list(p) for p in prompts]
    pos = np.zeros((slots,), np.int32)
    for j in range(steps):
        tok = np.zeros((slots, 1), np.int32)
        for i, slot in enumerate(rows_at):
            seqs[i].append(int(np.argmax(got[i][len(seqs[i]) - 1])))
            tok[slot, 0], pos[slot] = seqs[i][-1], len(seqs[i]) - 1
        engine._cache, logits = step(params, engine._cache, tok,
                                     pos.copy(), active,
                                     engine._page_table)
        logits = np.asarray(logits, np.float32)
        for i, slot in enumerate(rows_at):
            got[i][len(seqs[i]) - 1] = logits[slot, 0]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())
    device = _device_record()
    del engine, params, model
    gc.collect()

    # -- the plain reference over the same tokens, float32 and float8 --
    ref_params = ref.make_params(cfg, "serve", seed)
    sizes = ref.sizes(cfg, "serve")
    failures, rows = [], []
    for i, seq in enumerate(seqs):
        keep = np.asarray(sorted(got[i]))
        padded = np.zeros(-(-len(seq) // 128) * 128 if len(seq) > 128
                          else len(seq), np.int32)
        padded[:len(seq)] = seq
        with jax.default_matmul_precision("highest"):
            want, low = (np.asarray(jax.jit(
                lambda p, t, prec=prec: ref.logits_fn(p, t, sizes, prec)[keep]
            )(ref_params, jnp.asarray(padded)), np.float32)
                for prec in ("float32", "fp8"))
        mine = np.stack([got[i][p] for p in keep])
        err, low_err = np.abs(mine - want), np.abs(low - want)
        served = np.asarray(seq[1:] + [0])[keep]
        row = {"latent": f"row {i}: {lens[i]} prefilled + {steps} decoded",
               "compared_rows": int(len(keep)),
               "max_abs_err": float(err.max()),
               "max_abs_err_prefill": float(err[keep < lens[i]].max()),
               "max_abs_err_decode": float(err[keep >= lens[i]].max()),
               "mean_abs_err": float(err.mean()),
               "max_abs_ref": float(np.abs(want).max()),
               "fp8_control_max_abs_err": float(low_err.max()),
               "argmax_agree": float(np.mean(mine.argmax(-1)
                                             == want.argmax(-1))),
               "served_logit_gap_max": float(np.max(
                   (want.max(-1) - want[np.arange(len(keep)), served])
                   [keep >= lens[i] - 1][:-1])),
               "atol": LATENT_LOGIT_TOL}
        rows.append(row)
        say(row)
        tol = 1e-3 if rehearse else LATENT_LOGIT_TOL
        if not (np.isfinite(mine).all() and err.max() <= tol):
            failures.append(row)
        if not rehearse and low_err.max() <= LATENT_LOGIT_TOL:
            failures.append({"latent": "the float8 control passes the "
                             "tolerance", **row})
    print(compile_stats_line(), flush=True)
    say({"phase": "latent", "ok": not failures, "failures": failures,
         "rows": rows, "device": device, "peak_bytes_in_use": int(peak),
         "prefill_seconds_with_setup": round(prefill_s, 1),
         "seconds": round(time.monotonic() - t0, 1)})
    return 0 if not failures else 1


def _kernels_child(rehearse: bool) -> int:
    import numpy as np

    import jax
    import jax.numpy as jnp

    from tpunet.ops.attention import dense_attention
    from tpunet.ops.flash import flash_attention
    from tpunet.parallel.dist import initialize_distributed
    from tpunet.utils.cache import (compile_stats_line,
                                    enable_persistent_compile_cache)

    t0 = time.monotonic()
    # Pod-or-not is decided from these two; on the one-host machine
    # initialize_distributed() must be a no-op (ISSUE 21 item 9).
    say({"TPU_WORKER_HOSTNAMES": os.environ.get("TPU_WORKER_HOSTNAMES"),
         "MEGASCALE_COORDINATOR_ADDRESS":
             os.environ.get("MEGASCALE_COORDINATOR_ADDRESS")})
    initialize_distributed()
    enable_persistent_compile_cache()
    interpret = bool(rehearse)
    failures: list = []
    rows: list = []

    def rnd(key, shape, dtype, scale=1.0):
        return (scale * jax.random.normal(jax.random.PRNGKey(key), shape)
                ).astype(dtype)

    def compare(kernel, name, got, want, rtol, atol):
        """np.testing.assert_allclose semantics, reported not raised:
        worst |got-want| / (atol + rtol*|want|) must be <= 1."""
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        err = np.abs(got - want)
        ratio = float(np.max(err / (atol + rtol * np.abs(want))))
        row = {"kernel": kernel, "tensor": name,
               "max_abs_err": float(err.max()),
               "max_abs_ref": float(np.abs(want).max()),
               "tol_ratio": round(ratio, 4), "rtol": rtol, "atol": atol}
        rows.append(row)
        say(row)
        if not (np.isfinite(got).all() and ratio <= 1.0):
            failures.append(row)

    # -- flash attention (plain + segmented) vs dense_attention ------
    # bf16 vs the f32 dense reference: rtol/atol 2e-2 on the value
    # (tests/test_attention.py:190-200), 5e-2 on dq/dk/dv (:300-308).
    fl_shapes = [(1, 128, 2, 32)] if rehearse else [
        (4, 2048, 16, 128), (4, 2048, 16, 64)]
    blk = dict(block_q=32, block_k=32) if rehearse else {}
    for b, t, hh, d in fl_shapes:
        q, k, v = (rnd(10 + i, (b, t, hh, d), jnp.bfloat16)
                   for i in range(3))
        seg = jnp.asarray(np.repeat(np.arange(4), t // 4)[None]
                          .repeat(b, 0), jnp.int32)
        for label, segs in (("flash", None), ("flash_seg", (seg, seg))):
            def f_flash(q, k, v):
                return flash_attention(q, k, v, causal=True,
                                       interpret=interpret,
                                       segment_ids=segs, **blk)

            def f_dense(q, k, v):
                with jax.default_matmul_precision("highest"):
                    return dense_attention(q, k, v, causal=True,
                                           segment_ids=segs)

            def vg(f, *xs):
                return jax.jit(jax.value_and_grad(
                    lambda *a: jnp.sum(f(*a).astype(jnp.float32)
                                       * jnp.cos(jnp.arange(
                                           d, dtype=jnp.float32))),
                    argnums=(0, 1, 2)))(*xs)
            out = jax.jit(f_flash)(q, k, v)
            f32 = [a.astype(jnp.float32) for a in (q, k, v)]
            ref = jax.jit(f_dense)(*f32)
            tag = f"{label}[{b}x{t}x{hh}x{d} causal bf16]"
            compare(tag, "out", out, ref, 2e-2, 2e-2)
            if label == "flash_seg" and not rehearse and d != 128:
                continue    # one segmented backward is enough
            _, g = vg(f_flash, q, k, v)
            _, gr = vg(f_dense, *f32)
            for name, a, r in zip(("dq", "dk", "dv"), g, gr):
                compare(tag, name, a, r, 5e-2, 5e-2)

    _paged_decode_check(rehearse, rows, failures)
    kernels_s = time.monotonic() - t0

    # -- the default Trainers' own steps hold the Pallas calls -------
    import gc

    from tpunet.config import config_from_args
    from tpunet.parallel import shard_host_batch
    from tpunet.train import metrics as M
    from tpunet.train.loop import Trainer
    from tpunet.utils.prng import step_key

    def trainer_step(label, argv, scope, run_one_step):
        """Lower the Trainer's own train_step on its own first batch,
        as bench.py does, and count the Pallas custom calls in the
        compiled text: some under ``scope``, or, where ``scope`` is
        None, none at all."""
        cfg = config_from_args(argv + [
            "--checkpoint-dir",
            os.path.join(os.environ["TPUNET_SMOKE_OUT"], "kernels")])
        trainer = Trainer(cfg)
        try:
            bx, by = next(iter(trainer._epoch_batches(0)))
            gx, gy = shard_host_batch(trainer.mesh, bx,
                                      by.astype(np.int32))
            t1 = time.monotonic()
            compiled = trainer.train_step.lower(
                trainer.state, gx, gy, step_key(cfg.seed, 0)).compile()
            lines = [ln for ln in compiled.as_text().splitlines()
                     if "tpu_custom_call" in ln]
            mem = compiled.memory_analysis()
            rec = {"train_step": label,
                   "compile_seconds": round(time.monotonic() - t1, 1),
                   "tpu_custom_calls": len(lines),
                   "memory_analysis": {
                       k: int(getattr(mem, k)) for k in (
                           "temp_size_in_bytes",
                           "argument_size_in_bytes",
                           "output_size_in_bytes",
                           "alias_size_in_bytes")
                       if hasattr(mem, k)}}
            held = lines if scope is None else [ln for ln in lines
                                                if scope in ln]
            if scope is not None:
                rec[f"{scope}_custom_calls"] = len(held)
            if not rehearse and bool(held) != (scope is not None):
                failures.append({label: f"{len(held)} tpu_custom_calls "
                                 f"({scope or 'any scope'}) in the "
                                 "Trainer's compiled step"})
            if run_one_step:
                # One real step, so the peak is a step's peak.
                _, metrics = trainer.train_step(
                    trainer.state, gx, gy, step_key(cfg.seed, 0))
                rec["loss"] = M.summarize(metrics)["loss"]
                if not np.isfinite(rec["loss"]):
                    failures.append({label: f"loss {rec['loss']}"})
            stats = jax.devices()[0].memory_stats() or {}
            rec["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
            rec["bytes_limit"] = stats.get("bytes_limit")
            say(rec)
        finally:
            trainer.close()

    # The same flags as the train_vision / train_lm children, --epochs
    # included (the LR schedule is part of the program): their train
    # steps then come out of the compile cache.
    vision = ["--preset", "single", "--mesh-data", "1", "--epochs", "1",
              "--dataset", "synthetic", "--synthetic-size", "512"]
    lm = ["--preset", "single", "--mesh-data", "1", "--epochs", "1",
          "--dataset", "synthetic_lm", "--model", "lm"]
    if rehearse:
        vision += ["--synthetic-size", "64", "--batch-size", "16",
                   "--image-size", "32", "--width-mult", "0.5"]
        lm += TINY_LM_WIDTH + ["--seq-len", "64", "--batch-size", "4",
                               "--synthetic-size", "16"]
    else:
        lm += LM_TRAIN_WIDTH + ["--seq-len", "2048", "--batch-size",
                                "8", "--synthetic-size", "32"]
    # The MobileNetV2 step calls no kernel: its convolutions, BatchNorm
    # and ReLU6 are the compiler's own fusions.
    trainer_step("mobilenet_v2_224_b128", vision, None, run_one_step=True)
    gc.collect()
    # Compile only: the train_lm phase runs it (and finds this compile
    # in the cache).
    trainer_step("lm_h2048_d8_t2048_b8", lm, "tpunet_flash",
                 run_one_step=False)
    print(compile_stats_line(), flush=True)
    say({"phase": "kernels", "ok": not failures, "failures": failures,
         "device": _device_record(), "comparisons": len(rows),
         "kernel_seconds": round(kernels_s, 1),
         "seconds": round(time.monotonic() - t0, 1)})
    return 0 if not failures else 1


def _dp_child(argv: list) -> int:
    """tpunet.main's flow with the readings the comparison needs: the
    initial eval, the shardings, and each device's bytes."""
    import jax

    from tpunet.config import config_from_args
    from tpunet.parallel import initialize_distributed
    from tpunet.parallel.mesh import mesh_shape_dict
    from tpunet.train.loop import Trainer
    from tpunet.utils import log0
    from tpunet.utils.cache import (compile_stats_line,
                                    enable_persistent_compile_cache)

    t0 = time.monotonic()
    say({"TPU_WORKER_HOSTNAMES": os.environ.get("TPU_WORKER_HOSTNAMES"),
         "MEGASCALE_COORDINATOR_ADDRESS":
             os.environ.get("MEGASCALE_COORDINATOR_ADDRESS")})
    initialize_distributed()
    enable_persistent_compile_cache()
    cfg = config_from_args(argv)
    trainer = Trainer(cfg)
    try:
        ev = trainer.evaluate()
        hist = trainer.train()
        param = jax.tree_util.tree_leaves(trainer.state.params)[0]
        from tpunet.parallel import shard_host_batch
        import numpy as np
        gx = shard_host_batch(trainer.mesh, np.zeros(
            (cfg.data.batch_size, 32, 32, 3), np.uint8))
        devices = []
        for d in jax.local_devices():
            st = d.memory_stats() or {}
            devices.append({
                "id": d.id,
                "bytes_in_use": int(st.get("bytes_in_use", 0)) or sum(
                    s.data.nbytes for leaf in jax.tree_util.tree_leaves(
                        trainer.state)
                    for s in leaf.addressable_shards if s.device == d),
                "peak_bytes_in_use": st.get("peak_bytes_in_use")})
        rec = {"phase": "dp", "device": _device_record(),
               "mesh": mesh_shape_dict(trainer.mesh),
               "global_batch": cfg.data.batch_size,
               "eval_loss": ev["loss"], "eval_count": ev["count"],
               "train_loss": hist[-1]["train_loss"],
               "test_loss": hist[-1]["test_loss"],
               "batch_sharding": str(gx.sharding.spec),
               "batch_sharding_devices": sorted(
                   s.device.id for s in gx.addressable_shards),
               "param_sharding": str(param.sharding.spec),
               "param_devices": sorted(
                   s.device.id for s in param.addressable_shards),
               "devices": devices,
               "seconds": round(time.monotonic() - t0, 1)}
    finally:
        trainer.close()
    log0(compile_stats_line())
    say(rec)
    return 0


# --------------------------------------------------------------------
# Parent
# --------------------------------------------------------------------

def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--multichip", action="store_true",
                   help="four chips: dp=4 vs dp=1 training, and four "
                        "one-chip replicas behind the router vs one")
    p.add_argument("--out", default=os.path.join(HERE, "chiprun_out",
                                                 "smoke"))
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="tiny sizes on JAX_PLATFORMS=cpu; never ok:true")
    p.add_argument("--phase", default="", help=argparse.SUPPRESS)
    p.add_argument("rest", nargs=argparse.REMAINDER,
                   help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args.phase == "kernels":
        return _kernels_child(args.rehearse_cpu)
    if args.phase == "latent":
        return _latent_child(args.rehearse_cpu)
    if args.phase == "dp":
        return _dp_child([a for a in args.rest if a != "--"])

    device = {"platform": None, "kind": None, "count": 0}
    bad_env = [k for k in FORBIDDEN_ENV if os.environ.get(k)]
    if bad_env:
        say(f"refusing to start: {bad_env} set — the smoke runs the "
            "kernels, never their references or the interpreter")
        say({"ok": False, "device": device})
        return 2

    ctx = Ctx(args)
    os.makedirs(ctx.out, exist_ok=True)
    os.environ["TPUNET_SMOKE_OUT"] = ctx.out
    if ctx.multichip:
        phases = [("dp", phase_dp), ("router", phase_router)]
    else:
        phases = [("kernels", phase_kernels),
                  ("latent", phase_latent),
                  ("train_vision", phase_train_vision),
                  ("train_lm", phase_train_lm),
                  ("serve", phase_serve)]

    ok = True
    summary = []
    for name, fn in phases:
        t0 = time.monotonic()
        try:
            result = fn(ctx)
            result["phase_seconds"] = round(time.monotonic() - t0, 1)
            say(result)
            summary.append({"phase": name, "ok": True,
                            "seconds": result["phase_seconds"]})
        except PhaseError as e:
            ok = False
            say(f"PHASE FAILED {name}: {e}")
            summary.append({"phase": name, "ok": False,
                            "seconds": round(time.monotonic() - t0, 1)})
            break       # no chip, or a broken program: stop spending
        except Exception as e:  # noqa: BLE001 — a smoke bug is a failure
            ok = False
            say(f"PHASE FAILED {name}: {type(e).__name__}: {e}")
            summary.append({"phase": name, "ok": False})
            break
    totals = {"total_seconds": round(time.monotonic() - ctx.t0, 1),
              "compile_seconds": round(ctx.compile_seconds, 1),
              "cache_hits": ctx.cache_hits}
    say({"summary": summary, **totals,
         "cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR")
         or os.path.join(HERE, ".jax_cache")})
    # A second run into the same --out (a warm cache) says how it
    # compares with the one before.
    last_path = os.path.join(ctx.out, "last_run.json")
    if ok and os.path.exists(last_path):
        with open(last_path) as f:
            say({"previous_run": json.load(f), "this_run": totals})
    if ok:
        with open(last_path, "w") as f:
            json.dump(totals, f)

    if ctx.devices:
        platforms = {d[0] for d in ctx.devices}
        kinds = {d[1] for d in ctx.devices}
        device = {"platform": sorted(platforms)[0],
                  "kind": sorted(kinds)[0],
                  "count": max(d[2] for d in ctx.devices)}
        if len(platforms) > 1 or len(kinds) > 1:
            ok = False
            say(f"children disagree on the device: {ctx.devices}")
    if not ctx.devices or device["platform"] != "tpu" or ctx.rehearse:
        # Never a pass off the chip; a rehearsal's exit code still
        # says whether its phases passed.
        say({"ok": False, "rehearsal": ctx.rehearse,
             "phases_passed": ok, "device": device})
        return 0 if (ctx.rehearse and ok) else 1
    say({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
