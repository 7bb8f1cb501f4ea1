"""Runner ``serve``: the program's ``Engine`` in-process, under load.

The engine is built as ``python -m tpunet.serve`` builds it (the HTTP
front end and the router are bypassed), with the weights made from the
seed. Load comes from this process: ``arrival: closed`` starts
``clients`` threads that each send their next request when the last one
finished; ``arrival: open`` sends on the seeded schedule whatever has
finished, times each request from when it was due, and prints how late
the generator ran. Clients take the engine's token events on their own
clock. Set-up warms one request per prefill bucket, then the traffic
runs for ``fill_seconds`` before the window opens; requests in flight
when it closes are drained and counted.

``correct``: once the window has closed and the engine is gone, the
reference stage runs the plain float32 model once over prompt + served
tokens of a seeded sample of finished requests (the longest among
them) and reads how far each served token's logit lies below that
position's best.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from benchmark import harness, trafficgen, weights
from benchmark import runners_common as common


class Load:
    """The client side: sends requests, stamps token events."""

    def __init__(self, engine, requests, traffic: dict):
        self.engine, self.requests, self.traffic = engine, requests, traffic
        self.lock = threading.Lock()
        self.next = 0
        self.records = []            # one dict per request sent
        self.stop_sending = threading.Event()
        self.threads = []
        self.late_s = []             # open loop: generator lateness

    def _take(self):
        with self.lock:
            if self.stop_sending.is_set() or self.next >= len(self.requests):
                return None, None
            i = self.next
            self.next += 1
            return i, self.requests[i]

    def _serve_one(self, i, spec, due=None) -> None:
        rec = {"index": i, "sent": time.perf_counter(), "due": due,
               "token_t": [], "tokens": [], "reason": None, "error": None,
               "queue_s": None, "prefill_s": None, "busy": None,
               "want": spec["max_new_tokens"]}
        with self.lock:
            self.records.append(rec)
        try:
            req = self.engine.submit(spec["prompt"],
                                     max_new_tokens=spec["max_new_tokens"],
                                     temperature=0.0)
        except Exception as e:  # noqa: BLE001 — a refusal is a failure
            rec["reason"], rec["error"] = "refused", repr(e)
            return
        try:
            for kind, val in req.events(
                    timeout=self.traffic["request_timeout_s"]):
                if kind == "token":
                    rec["token_t"].append(time.perf_counter())
                    rec["tokens"].append(int(val))
                else:
                    rec["reason"] = val
        except TimeoutError as e:
            req.cancel()
            rec["reason"], rec["error"] = "timeout", repr(e)
            return
        rec["error"] = req.error
        rec["queue_s"], rec["prefill_s"] = req.queue_s, req.prefill_s
        rec["busy"] = self.engine.active_slots()

    def _closed_client(self) -> None:
        while True:
            i, spec = self._take()
            if spec is None:
                return
            self._serve_one(i, spec)

    def _open_sender(self, t_start: float) -> None:
        while True:
            i, spec = self._take()
            if spec is None:
                return
            due = t_start + spec["due_s"]
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self.late_s.append(max(0.0, time.perf_counter() - due))
            t = threading.Thread(target=self._serve_one,
                                 args=(i, spec, due), daemon=True)
            t.start()
            with self.lock:
                self.threads.append(t)

    def start(self) -> float:
        t_start = time.perf_counter()
        if self.traffic["arrival"] == "closed":
            new = [threading.Thread(target=self._closed_client, daemon=True)
                   for _ in range(self.traffic["clients"])]
        else:
            new = [threading.Thread(target=self._open_sender,
                                    args=(t_start,), daemon=True)]
        self.threads.extend(new)
        for t in new:
            t.start()
        return t_start

    def finish(self, timeout: float) -> bool:
        """Stop sending, wait for what is in flight."""
        self.stop_sending.set()
        deadline = time.perf_counter() + timeout
        while True:
            with self.lock:
                alive = [t for t in self.threads if t.is_alive()]
            if not alive:
                return True
            if time.perf_counter() > deadline:
                return False
            alive[0].join(timeout=0.5)


def window_metrics(records, t_open: float, t_close: float, slots: int):
    """End-to-end and host-side per-layer numbers of one window."""
    inside = [r for r in records
              if t_open <= (r["due"] or r["sent"]) < t_close]
    failed = [r for r in inside
              if r["reason"] != "length" or r["error"]
              or len(r["tokens"]) != r["want"]]
    tokens = sum(1 for r in records for t in r["token_t"]
                 if t_open <= t < t_close)
    ttft = [1e3 * (r["token_t"][0] - (r["due"] or r["sent"]))
            for r in inside if r["token_t"]]
    itl = [1e3 * (b - a) for r in inside
           for a, b in zip(r["token_t"], r["token_t"][1:])]
    done = [r for r in inside if r not in failed]
    host = {
        "queue_ms": (1e3 * harness.median([r["queue_s"] for r in done
                                           if r["queue_s"] is not None])
                     if done else None),
        "prefill_ms": (1e3 * harness.median([r["prefill_s"] for r in done
                                             if r["prefill_s"] is not None])
                       if done else None),
        "slots_busy_pct": (100.0 * sum(r["busy"] for r in done)
                           / (len(done) * slots) if done else None),
    }
    metrics = {
        "serve_tok_per_s": tokens / (t_close - t_open),
        "ttft_p95_ms": harness.percentile(ttft, 95) if ttft else None,
        "itl_p95_ms": harness.percentile(itl, 95) if itl else None,
    }
    return inside, failed, done, metrics, host


def build_engine(cell: dict, seed: int):
    from tpunet.config import ModelConfig, ServeConfig
    from tpunet.models import create_model
    from tpunet.serve.engine import Engine

    config = cell["config"]
    model = create_model(ModelConfig(**config["program"]["model"]))
    ref = harness.load_reference(cell)
    spec = ref.param_spec(config, cell["cell"]["section"])
    params = weights.make_tree(spec, seed,
                               dtype=config["program"]["model"]["param_dtype"])
    serve = dict(cell["cell"]["program"]["serve"])
    if "prefill_buckets" in serve:
        serve["prefill_buckets"] = tuple(serve["prefill_buckets"])
    return Engine(model, {"params": params}, ServeConfig(**serve))


def warm_up(engine, traffic: dict, config: dict, seed: int) -> None:
    """One request per prefill bucket (the longest prompt it takes),
    a few decode steps each: every program the window will run."""
    r = trafficgen.rng_for(seed, 5)
    top = traffic["prompt_len"]["max"]
    for bucket in engine.buckets:
        n = min(bucket, top)
        req = engine.submit(r.integers(0, config["vocab_size"], size=n)
                            .astype(np.int32), max_new_tokens=4,
                            temperature=0.0)
        req.result(timeout=1100.0)
        if req.finish_reason != "length" or req.error:
            raise SystemExit(f"warm-up request failed: {req.finish_reason} "
                             f"{req.error}")
        if n == top:
            break


def program(ctx: dict) -> dict:
    import jax

    cell = ctx["cell"]
    config, traffic = cell["config"], cell["traffic"]
    harness.enable_cache(program=True)
    harness.require_chips(cell["entry"]["chips"], ctx["rehearse"])
    from tpunet.utils.cache import _COMPILES

    seed = ctx["seed"]
    window = common.window_seconds(ctx)
    engine = build_engine(cell, seed).start()
    try:
        warm_up(engine, traffic, config, seed)
        fill = traffic.get("fill_seconds", 0.0)
        n_req = int(traffic.get("max_requests", 4096))
        load = Load(engine, trafficgen.serve_requests(
            traffic, config, seed, n_req), traffic)
        load.start()
        time.sleep(fill)
        compiles0 = _COMPILES["programs"]
        setup_s = time.time() - ctx["t0"]
        with common.tracing(ctx) as tracer:
            t_open = time.perf_counter()
            time.sleep(window)
            t_close = time.perf_counter()
        compiles = _COMPILES["programs"] - compiles0
        drained = load.finish(timeout=traffic["request_timeout_s"])
        stats = jax.devices()[0].memory_stats() or {}
        device = harness.device_record()
    finally:
        engine.stop()

    inside, failed, done, metrics, host = window_metrics(
        load.records, t_open, t_close, engine.slots)
    if not drained:
        raise SystemExit("requests still in flight after the drain timeout")
    metrics["setup_s"] = setup_s
    host["ttft_p95_ms"] = metrics.pop("ttft_p95_ms")
    host["compiles_in_window"] = compiles
    host["peak_hbm_pct"] = common.hbm_pct(stats)
    if load.late_s:
        harness.say(f"generator lateness: median "
                    f"{1e3 * harness.median(load.late_s):.3f} ms, max "
                    f"{1e3 * max(load.late_s):.3f} ms")
    # what `correct` reads: a seeded sample of the finished requests
    finished = [r for r in load.records
                if r["reason"] == "length" and not r["error"]]
    missing = sum(r["want"] - len(r["tokens"]) for r in finished)
    sample = []
    if finished:
        sizes = [len(load.requests[r["index"]]["prompt"]) + len(r["tokens"])
                 for r in finished]
        picks = trafficgen.sample_indices(
            len(finished), int(np.argmax(sizes)),
            traffic["sample_requests"], seed)
        sample = [finished[i] for i in picks]
    np.savez(os.path.join(ctx["workdir"], "capture.npz"),
             **{f"prompt{j}": load.requests[r["index"]]["prompt"]
                for j, r in enumerate(sample)},
             **{f"tokens{j}": np.asarray(r["tokens"], np.int32)
                for j, r in enumerate(sample)})
    result = {"attempted": len(inside), "failed": len(failed),
              "numbers": {"tokens_missing": missing,
                          "sampled_requests": len(sample)},
              "device": device, "metrics": metrics, "window_s": window,
              "finished": len(finished)}
    common.close_result(ctx, result, host, tracer, t_close - t_open)
    harness.say(f"window {t_close - t_open:.3f} s: {len(inside)} requests "
                f"sent in it, {len(failed)} failed, {len(finished)} finished "
                f"in the run, setup {setup_s:.1f} s, compiles in window "
                f"{compiles}, metrics {result['metrics']}, host {host}")
    return result


def reference(ctx: dict, prog: dict) -> dict:
    import jax
    import jax.numpy as jnp

    cell = ctx["cell"]
    config, traffic = cell["config"], cell["traffic"]
    section = cell["cell"]["section"]
    harness.enable_cache(program=False)
    harness.require_chips(cell["entry"]["chips"], ctx["rehearse"])
    ref = harness.load_reference(cell)
    cap = np.load(os.path.join(ctx["workdir"], "capture.npz"))
    n = prog["numbers"]["sampled_requests"]
    pad = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    pad = -(-pad // 128) * 128 if pad > 128 else pad
    t = time.time()
    worst = low_worst = 0.0
    compared = 0
    with jax.default_matmul_precision("highest"):
        params = ref.make_params(config, section, ctx["seed"])
        gaps_fn = ref.token_gaps_fn(config, section)
        for j in range(n):
            prompt, served = cap[f"prompt{j}"], cap[f"tokens{j}"]
            seq = np.zeros(pad, np.int32)
            seq[:len(prompt)] = prompt
            seq[len(prompt):len(prompt) + len(served)] = served
            gap, low = gaps_fn(params, jnp.asarray(seq),
                               ctx["control"] or "float32")
            lo, hi = len(prompt) - 1, len(prompt) + len(served) - 1
            worst = max(worst, float(jnp.max(gap[lo:hi])))
            low_worst = max(low_worst, float(jnp.max(low[lo:hi])))
            compared += hi - lo
    harness.say(f"reference: {n} requests, {compared} served tokens in "
                f"{time.time() - t:.1f} s")
    numbers = {"served_logit_gap_max": worst if n else float("inf"),
               "tokens_missing": prog["numbers"]["tokens_missing"],
               "failed_requests": prog["failed"]}
    out = {"correct": common.compare(numbers, cell["cell"].get("limits", {})),
           "numbers": numbers, "compared_tokens": compared}
    if ctx["control"]:
        out["control"] = {"served_logit_gap_max": low_worst}
        harness.say(f"control {ctx['control']} served_logit_gap_max = "
                    f"{low_worst!r}")
    return out
