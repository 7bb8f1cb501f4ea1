"""Of the first device's idle seconds — every gap between its merged
operation intervals, not the five longest of the breakdown — the share
that no host span of the program (``tpunet/*``, ``train``) covers by at
least half: idle time the program's own spans do not explain. Host and
device events share the profiler's clock."""

from benchmark import trace_reduce


def unattributed_pct(trace) -> float | None:
    if not trace.device_ops:
        return None
    ops = trace.device_ops[sorted(trace.device_ops)[0]]
    merged = trace_reduce.merge_intervals((s, s + d) for _, s, d in ops)
    gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
    spans = sorted((s, s + d) for _, s, d in trace.host_spans)
    idle = unexplained = 0.0
    live, nxt = [], 0                  # spans that may still reach a gap
    for g0, g1 in gaps:
        while nxt < len(spans) and spans[nxt][0] < g1:
            live.append(spans[nxt])
            nxt += 1
        live = [sp for sp in live if sp[1] > g0]
        cover = max((min(g1, e) - max(g0, s) for s, e in live), default=0.0)
        idle += g1 - g0
        if cover < 0.5 * (g1 - g0):
            unexplained += g1 - g0
    return 100.0 * unexplained / idle if idle > 0 else None


def read(obs: dict, params: dict):
    if obs.get("trace") is None:
        return None
    return unattributed_pct(obs["trace"])
