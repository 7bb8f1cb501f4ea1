"""How full the serve window's device calls were, from what the runner
and the engine already keep: no counter is added to the engine for it.

``obs`` carries neither the requests nor the window's edges, and the
runner may not be edited, so this reader takes them from the frame of
``runners/serve.py``'s ``program`` that called it: ``load.records``
(per request ``sent`` and the engine's own ``queue_s`` stamp, so that
``sent + queue_s`` is the request's ``prefill_start_t`` to within the
microseconds ``engine.submit`` takes), ``load.requests`` (prompts),
``engine`` (``slots``, ``bucket_for``) and ``t_open`` / ``t_close``.
A prefill call stamps every row it admits with one ``t0``, so rows of
one bucket whose estimates lie within ``params["same_call_s"]`` are one
call (two calls are a whole prefill apart). ``params["value"]``:

- ``rows_per_call``: requests admitted in the window / prefill calls;
- ``useful_tok_pct``: their prompt tokens / sum over those calls of
  slots x bucket;
- ``live_rows_pct``: tokens that decode steps produced in the window
  (every token event in it but a request's first, which its prefill
  produced) / (``tpunet/serve_decode`` spans in the trace x slots).

Without the runner's frame or (``live_rows_pct``) a trace: ``None``.
"""

import sys

from benchmark import harness

WANTED = ("engine", "load", "t_open", "t_close")


def runner_locals():
    frame = sys._getframe(1)
    while frame is not None:
        if all(k in frame.f_locals for k in WANTED):
            return frame.f_locals
        frame = frame.f_back
    return None


def prefill_calls(records, requests, bucket_for, t_open, t_close,
                  same_call_s):
    """``[{"bucket", "t0", "prompts": [tokens]}]`` of the calls whose
    rows were admitted inside the window."""
    rows = sorted((r["sent"] + r["queue_s"],
                   len(requests[r["index"]]["prompt"]))
                  for r in records if r["queue_s"] is not None)
    calls = []
    for t0, n in rows:
        if not t_open <= t0 < t_close:
            continue
        bucket = bucket_for(n)
        same = next((c for c in reversed(calls) if c["bucket"] == bucket
                     and t0 - c["t0"] < same_call_s), None)
        if same is None:
            calls.append({"bucket": bucket, "t0": t0, "prompts": [n]})
        else:
            same["prompts"].append(n)
    return calls


def decode_tokens(records, t_open, t_close) -> int:
    return sum(1 for r in records for t in r["token_t"][1:]
               if t_open <= t < t_close)


def read(obs: dict, params: dict):
    have = runner_locals()
    if have is None:
        return None
    engine, load = have["engine"], have["load"]
    t_open, t_close = have["t_open"], have["t_close"]
    if params["value"] == "live_rows_pct":
        if obs.get("trace") is None:
            return None
        steps = sum(1 for name, _, _ in obs["trace"].host_spans
                    if name == "tpunet/serve_decode")
        if not steps:
            return None
        return 100.0 * decode_tokens(load.records, t_open, t_close) \
            / (steps * engine.slots)
    calls = prefill_calls(load.records, load.requests, engine.bucket_for,
                          t_open, t_close, params["same_call_s"])
    if not calls:
        return None
    rows = sum(len(c["prompts"]) for c in calls)
    if params["value"] == "rows_per_call":
        if obs.get("trace") is not None:
            spans = sum(1 for name, _, _ in obs["trace"].host_spans
                        if name == "tpunet/serve_prefill")
            harness.say(f"prefill calls in the window: {len(calls)} by the "
                        f"requests' stamps ({rows} rows), {spans} "
                        f"tpunet/serve_prefill spans in the trace, "
                        f"{engine.registry.counter('serve_prefills_total').value:.0f}"
                        f" serve_prefills_total since the engine started")
        return rows / len(calls)
    if params["value"] == "useful_tok_pct":
        return 100.0 * sum(sum(c["prompts"]) for c in calls) \
            / sum(engine.slots * c["bucket"] for c in calls)
    raise ValueError(f"unknown value {params['value']!r}")
