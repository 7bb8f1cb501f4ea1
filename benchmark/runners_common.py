"""What the runners share: the window's length and profiler, closing a
result (trace reduction, per-layer metrics, no timed number from a
rehearsal), and printing each compared number beside its limit."""

from __future__ import annotations

import contextlib
import os

from benchmark import harness


def window_seconds(ctx: dict) -> float:
    """``--seconds``; a traced run measures the traffic file's shorter
    ``trace_seconds`` (a trace of the whole window is too large)."""
    if not ctx["trace"]:
        return ctx["seconds"]
    return min(ctx["seconds"],
               ctx["cell"]["traffic"].get("trace_seconds", ctx["seconds"]))


def tracing(ctx: dict):
    """The profiler window of a traced run, else a context that does
    nothing (and has no ``path``)."""
    if ctx["trace"]:
        return harness.TraceWindow(os.path.join(ctx["workdir"], "trace"))
    return contextlib.nullcontext()


def hbm_pct(stats: dict):
    if not stats.get("bytes_limit"):
        return None
    return 100.0 * harness.peak_bytes(stats) / stats["bytes_limit"]


def close_result(ctx: dict, result: dict, host: dict, tracer,
                 elapsed: float) -> dict:
    """Add what a traced run reads (device busy seconds, the breakdown,
    the cell's per-layer metrics) to ``result``; a rehearsal keeps the
    metrics' names and none of their values."""
    cell = ctx["cell"]
    obs = {"host": host, "trace": None, "window_s": elapsed, "cell": cell,
           "device_kind": result["device"]["kind"]}
    path = getattr(tracer, "path", None)
    if path and not ctx["rehearse"]:
        from benchmark import trace_reduce
        obs["trace"] = trace_reduce.load(path)
        busy, win = trace_reduce.busy_and_window(obs["trace"], elapsed)
        result["device"].update(busy_s=busy, window_s=win)
        result["breakdown"] = trace_reduce.breakdown(obs["trace"])
    if ctx["trace"]:
        result["metrics"].update(read_layer_metrics(cell, obs))
    if ctx["rehearse"]:                  # no CPU number under a device name
        result["metrics"] = {k: None for k in result["metrics"]}
    return result


def read_layer_metrics(cell: dict, obs: dict) -> dict:
    out = {}
    for m in cell["per_layer"]:
        value = harness.load_reader(cell, m["reader"]).read(
            obs, m.get("params", {}))
        if value is not None:
            out[m["name"]] = value
    return out


def compare(numbers: dict, limits: dict) -> bool:
    """Print each number beside its limit; all have to hold."""
    ok = True
    for name, value in numbers.items():
        if name.endswith("_leaf"):
            continue
        limit = limits.get(name)
        holds = limit is not None and value <= limit
        ok = ok and holds
        where = numbers.get(name + "_leaf", "")
        harness.say(f"compared {name} = {value!r} limit {limit!r} "
                    f"{'ok' if holds else 'FAILS'} {where}")
    return ok
