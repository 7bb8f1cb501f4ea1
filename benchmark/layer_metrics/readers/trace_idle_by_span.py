"""The first device's idle seconds by what the host was doing in them,
in points of the traced window.

The gaps are those between the first device's merged operation
intervals (as ``trace_idle_unattributed`` takes them). Every instant of
a gap goes to the INNERMOST host span of the program (``tpunet/*``,
``train``) open at that instant: of the spans that cover it, the one
that started last. A span's children take their part of it; what is
left is its self time. Host and device events share the profiler's
clock, and the program opens its spans on one thread, so containment
is enough.

``params["spans"]`` is a list of span names: 100 x the gap seconds
given to them / ``obs["window_s"]``; ``null``: the gap seconds under NO
span. Metrics whose lists partition the program's span names therefore
add up, with the ``null`` one, to the gaps' share of the window —
``device_idle_pct.*`` less the window's two edges. A name that never
occurs reads 0; without a device in the trace: ``None``.
"""

from benchmark import trace_reduce


def gap_seconds_by_span(trace) -> dict | None:
    """``{span name, or None for no span: idle seconds given to it}``."""
    if not trace.device_ops:
        return None
    ops = trace.device_ops[sorted(trace.device_ops)[0]]
    merged = trace_reduce.merge_intervals((s, s + d) for _, s, d in ops)
    spans = sorted((s, s + d, name) for name, s, d in trace.host_spans)
    given: dict = {None: 0.0}
    live, nxt = [], 0                  # spans that may still reach a gap
    for (_, g0), (g1, _) in zip(merged, merged[1:]):
        while nxt < len(spans) and spans[nxt][0] < g1:
            live.append(spans[nxt])
            nxt += 1
        live = [sp for sp in live if sp[1] > g0]
        cuts = sorted({g0, g1, *(t for s, e, _ in live for t in (s, e)
                                 if g0 < t < g1)})
        for a, b in zip(cuts, cuts[1:]):
            # ``live`` is in order of start: the last cover is innermost
            name = next((n for s, e, n in reversed(live)
                         if s <= a and e >= b), None)
            given[name] = given.get(name, 0.0) + (b - a)
    return given


def read(obs: dict, params: dict):
    if obs.get("trace") is None or not obs.get("window_s"):
        return None
    given = gap_seconds_by_span(obs["trace"])
    if given is None:
        return None
    names = params["spans"]
    seconds = given[None] if names is None else sum(
        given.get(name, 0.0) for name in names)
    return 100.0 * seconds / obs["window_s"]
