"""The device's time by the program's own scopes, for the readers that
report it (``layer_metrics/readers/scope_*.py``).

The reduction is the program's (``tpunet/obs/device_time.py``: the
trace's ``XLA Modules`` and ``XLA Ops`` lines against the scope table
of the programs' optimized HLO texts); this file finds its inputs and
keeps one result per process, since a dozen metrics read the same
trace. ``obs`` carries no trace path, so the xplane is looked for under
the child's own ``--workdir`` argument (``run.py`` passes it; the
profiler window writes ``trace/**/*.xplane.pb`` there). The scope
lists are data: ``benchmark/scopes.json``, one family per kind of cell.

``table`` returns ``None`` — and every reader then leaves its metric
out — when there is no trace (``--trace 0``, a rehearsal), or when the
program has no scope table (a checkout older than PR 25).
"""

from __future__ import annotations

import functools
import glob
import os
import re
import sys

from benchmark import harness


def xplane_path(argv=None):
    """The newest xplane under ``<--workdir>/trace``, or ``None``."""
    argv = sys.argv if argv is None else argv
    if "--workdir" not in argv[:-1]:
        return None
    workdir = argv[argv.index("--workdir") + 1]
    found = sorted(glob.glob(os.path.join(workdir, "trace", "**",
                                          "*.xplane.pb"), recursive=True))
    return found[-1] if found else None


def scopes_for(family: str):
    """A family's scopes as the program's reduction takes them:
    ``[(label, regular expression)]``, or for ``"phase_of"`` the
    program's own phase classifier with its catch-all ``other`` read
    as no scope at all."""
    spec = harness.load_json("benchmark", "scopes.json")[family]
    if spec != "phase_of":
        return [tuple(pair) for pair in spec]
    from tpunet.obs.hlo_bytes import phase_of

    def phase(path: str):
        found = phase_of(path)
        return None if found == "other" else found
    return phase


@functools.lru_cache(maxsize=None)
def table(family: str):
    """``device_time_by_scope`` of this run's trace under ``family``'s
    scopes, or ``None`` (see the module's text)."""
    path = xplane_path()
    if path is None:
        return None
    try:
        from tpunet.obs import device_time
    except ImportError:
        return None
    try:
        texts = device_time.program_texts()
        if not texts:
            return None
        scopes = scopes_for(family)
        tab = device_time.device_time_by_scope(path, texts, scopes)
    except Exception as e:  # noqa: BLE001 — a metric left out, not a run lost
        harness.say(f"scope table of {family!r} not read: {e!r}")
        return None
    for line in describe(tab, device_time.classifier(scopes)):
        harness.say(line)
    return tab


def describe(tab: dict, classify, top: int = 6):
    """The run's log lines: per program its executions, their median
    device time and the scopes' seconds; then its longest operations
    with the end of each one's path (what ``fusion.59`` is, in the
    program's own words) and the longest that no scope claims."""
    def tails(ops):
        return [(name, path[-70:], round(s, 5)) for s, name, path in ops]

    for label, prog in tab.items():
        runs = prog["executions"]
        by_scope: dict = {}
        for e in runs:
            for scope, s in e["by_scope"].items():
                by_scope[scope] = by_scope.get(scope, 0.0) + s
        ops = sorted(((s, name, path) for (name, path), s
                      in prog["ops"].items()), reverse=True)
        loose = [op for op in ops if not op[2] or classify(op[2]) is None]
        yield (f"program {label or '(outside every execution)'}: "
               f"{len(runs)} executions, median device "
               f"{1e3 * harness.median([e['device_s'] for e in runs]):.3f} "
               f"ms, op seconds {sum(e['op_s'] for e in runs):.4f}, "
               f"by scope { {k: round(v, 4) for k, v in by_scope.items()} }"
               f", unscoped {sum(e['unscoped_s'] for e in runs):.4f}")
        if label and "(" not in label:
            yield f"program {label}: longest {tails(ops[:top])}"
            yield f"program {label}: longest unscoped {tails(loose[:top])}"


def executions(tab: dict, program: str):
    """The executions of every program whose label matches ``program``
    (a regular expression; a label with ``(`` in it is a program the
    table does not hold, under the trace's own name)."""
    rx = re.compile(program)
    return [e for label, prog in tab.items() if label and rx.search(label)
            for e in prog["executions"]]


def median_ms(tab: dict, program: str, take) -> float | None:
    """Median over the matching executions of the device time, in ms,
    under the scopes ``take`` (a list of labels), or of the whole
    execution's ``XLA Modules`` event where ``take`` is ``"device"``."""
    runs = executions(tab, program)
    if not runs:
        return None
    if take == "device":
        return 1e3 * harness.median([e["device_s"] for e in runs])
    return 1e3 * harness.median(
        [sum(e["by_scope"].get(label, 0.0) for label in take) for e in runs])


def share_pct(tab: dict, take, of) -> float | None:
    """Over every execution of every program, and the operations
    outside all of them: the time under ``take`` as a share of the
    time under ``of``. Either is a list of scope labels, ``"unscoped"``
    (no path in the table, or a path no scope claims) or ``"ops"``
    (all operation time)."""
    def total(what):
        runs = [e for prog in tab.values() for e in prog["executions"]]
        if what == "ops":
            return sum(e["op_s"] for e in runs)
        if what == "unscoped":
            return sum(e["unscoped_s"] for e in runs)
        return sum(e["by_scope"].get(label, 0.0)
                   for e in runs for label in what)
    whole = total(of)
    return 100.0 * total(take) / whole if whole > 0 else None
