"""Device time by the program's own scopes, from a profiler trace.

Three pieces, all off every step path:

- a process-global, **lazy** table of the program's hot programs:
  ``Trainer`` and ``Engine`` each ``register_programs`` one
  zero-argument callable at construction; nothing is lowered, compiled
  or turned into text until somebody calls ``program_texts()`` (a
  traced benchmark run after its window, ``--obs-hbm-attrib``, the
  profiler window's close, ``scripts/roofline_attrib.py``).
- ``device_time_by_scope``: the trace's ``XLA Modules`` line names the
  program of every execution, its ``XLA Ops`` line the instruction of
  every device operation; ``hlo_bytes.op_scopes`` of the program's
  optimized HLO text gives each instruction its ``jax.named_scope`` /
  flax module path. ``jax.profiler.ProfileData`` alone, no ``xprof``.
- ``phase_times``: the fwd / bwd / optimizer / ema / eval split that
  ``scripts/obs_report.py --trace`` prints, over the rows
  ``op_rows`` makes of a trace directory (the texts lie beside the
  xplane as ``*.hlo.txt``, written by ``write_program_texts``).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import weakref
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

from tpunet.obs.hlo_bytes import op_scopes, phase_of

PHASES = ("augment", "fwd", "bwd", "optimizer", "ema", "eval", "other")

_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+")
_MODULE_NAME = re.compile(r"^HloModule\s+([\w.\-]+)")
_DIMS = re.compile(r"\[([0-9,]*)\]")

# -- the lazy table ----------------------------------------------------------

_PROVIDERS: list = []      # weak references to bound methods


def register_programs(provider: Callable[[], Dict[str, str]]) -> None:
    """Remember a bound method that returns ``{label: optimized HLO
    text}``. Called once per ``Trainer`` / ``Engine``, at construction;
    the method is not called here, and its object is not kept alive."""
    _PROVIDERS.append(weakref.WeakMethod(provider))


def module_name(hlo_text: str) -> str:
    """``HloModule jit_train_fn, ...`` -> ``jit_train_fn``."""
    found = _MODULE_NAME.match(hlo_text)
    return found.group(1) if found else ""


def program_texts() -> Dict[str, str]:
    """Evaluate every registered method whose object is alive (this is
    where programs are lowered, with a warm compile cache). A label is
    the HLO module's name, plus ``/w<width>`` where one jitted function
    holds several programs; a later registration wins a shared label."""
    texts: Dict[str, str] = {}
    live = [(ref, ref()) for ref in _PROVIDERS]
    _PROVIDERS[:] = [ref for ref, method in live if method is not None]
    for _, method in live:
        if method is not None:
            texts.update(method())
    return texts


def write_program_texts(directory: str) -> List[str]:
    """``<directory>/<label>.hlo.txt`` for every registered program, so
    that a kept trace can be read without the process that made it."""
    paths = []
    for label, text in program_texts().items():
        path = os.path.join(directory, label.replace("/", ".") + ".hlo.txt")
        with open(path, "w") as f:
            f.write(text)
        paths.append(path)
    return paths


# -- trace x scope table -----------------------------------------------------

Scopes = Union[Callable[[str], Optional[str]], Sequence[Tuple[str, str]]]


def classifier(scopes: Scopes) -> Callable[[str], Optional[str]]:
    """``op_name path -> scope label or None`` from either form of
    ``scopes`` that ``device_time_by_scope`` takes."""
    if callable(scopes):
        return scopes
    compiled = [(label, re.compile(rx)) for label, rx in scopes]

    def first_match(path: str) -> Optional[str]:
        for label, rx in compiled:
            if rx.search(path):
                return label
        return None
    return first_match


def _self_times(ops: List[Tuple[float, float, str]]):
    """``(start, own seconds, name)`` per operation: every instant of
    the line goes to the operation that started last among those
    running, so an operation that holds others (a ``while``, a
    ``conditional``) or overlaps the next keeps only the time they
    leave, and the own times add up to the line's busy time."""
    out, stack = [], []               # stack of (end, index into out)
    now = 0.0

    def advance(until: float) -> None:
        nonlocal now
        while stack and now < until:
            end, i = stack[-1]
            if end <= now:
                stack.pop()
                continue
            upto = min(end, until)
            out[i][1] += upto - now
            now = upto
        now = max(now, until)

    for start, dur, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        advance(start)
        out.append([start, 0.0, name])
        stack.append((start + dur, len(out) - 1))
    advance(float("inf"))
    return out


def _signature(instr_text: str) -> Tuple[str, str]:
    """``(name, dims of the result)`` of an instruction as the trace or
    the HLO text prints it: two programs of one jitted function (the
    engine's token widths) share instruction names, not shapes."""
    head, _, rest = instr_text.partition(" = ")
    dims = _DIMS.search(rest)
    return (head.split()[-1].lstrip("%") if head.strip() else "",
            dims.group(1) if dims else "")


def _match_program(module: str, seen: set, signatures: Dict[str, set],
                   names: Dict[str, str]) -> Optional[str]:
    """The label whose text holds the operations the executions
    showed; only texts of that HLO module are candidates."""
    best, score = None, 0.5
    for label, sigs in signatures.items():
        if names[label] != module or not seen:
            continue
        hit = len(seen & sigs) / len(seen)
        if hit > score:
            best, score = label, hit
    return best


def device_time_by_scope(xplane_path: str, texts: Dict[str, str],
                         scopes: Scopes) -> Dict[str, dict]:
    """Per program, what its executions cost the device and under which
    scope. ``scopes`` is a classifier ``op_name path -> label or
    None``, or ``[(label, regular expression)]`` of which the first
    that matches the path wins.

    -> ``{label: {"executions": [{"run_id", "start_s", "device_s",
    "op_s", "by_scope": {label: s}, "unscoped_s"}], "ops": {(name,
    path): s}}}``. ``device_s`` is the ``XLA Modules`` event's
    duration, ``op_s`` the operations' self time inside it,
    ``unscoped_s`` the part of it whose instruction has no path in the
    table or whose path no scope claims. A program the table does not
    hold appears under the trace's own name (``jit_f(<fingerprint>)``)
    with everything unscoped; operations outside every execution under
    ``""``. Devices are pooled."""
    from jax.profiler import ProfileData

    classify = classifier(scopes)
    tables = {label: op_scopes(text) for label, text in texts.items()}
    signatures = {label: {_signature(line) for line in text.splitlines()
                          if " = " in line}
                  for label, text in texts.items()}
    names = {label: module_name(text) for label, text in texts.items()}
    out: Dict[str, dict] = {}
    for plane in ProfileData.from_file(xplane_path).planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        modules, ops, seen = [], [], {}
        for line in plane.lines:
            if line.name == "XLA Modules":
                modules = sorted(
                    (ev.start_ns * 1e-9, ev.duration_ns * 1e-9, ev.name,
                     dict(ev.stats).get("run_id")) for ev in line.events)
            elif line.name == "XLA Ops":
                ops = [(ev.start_ns * 1e-9, ev.duration_ns * 1e-9,
                        _signature(ev.name)) for ev in line.events]
        starts = [m[0] for m in modules]
        per_exec: List[list] = [[] for _ in modules]
        outside = []
        for start, self_s, (name, dims) in _self_times(ops):
            i = bisect.bisect_right(starts, start) - 1
            if i >= 0 and start < modules[i][0] + modules[i][1]:
                per_exec[i].append((name, self_s))
                seen.setdefault(modules[i][2], set()).add((name, dims))
            else:
                outside.append((name, self_s))
        matched = {traced: _match_program(traced.split("(", 1)[0], sigs,
                                          signatures, names)
                   for traced, sigs in seen.items()}
        for (start, dur, traced, run_id), inside in zip(modules, per_exec):
            label = matched.get(traced)
            _account(out, label or traced, tables.get(label, {}), classify,
                     inside, {"run_id": run_id, "start_s": start,
                              "device_s": dur})
        if outside:
            _account(out, "", {}, classify, outside,
                     {"run_id": None, "start_s": None, "device_s": 0.0})
    return out


def _account(out: dict, label: str, table: Dict[str, str], classify,
             inside, execution: dict) -> None:
    prog = out.setdefault(label, {"executions": [], "ops": {}})
    by_scope: Dict[str, float] = {}
    op_s = unscoped = 0.0
    for name, self_s in inside:
        path = table.get(name, "")
        scope = classify(path) if path else None
        op_s += self_s
        if scope is None:
            unscoped += self_s
        else:
            by_scope[scope] = by_scope.get(scope, 0.0) + self_s
        key = (name, path)
        prog["ops"][key] = prog["ops"].get(key, 0.0) + self_s
    prog["executions"].append({**execution, "op_s": op_s,
                               "by_scope": by_scope,
                               "unscoped_s": unscoped})


# -- the operator's phase table ---------------------------------------------

def op_rows(trace_dir: str, texts: Optional[Dict[str, str]] = None
            ) -> List[dict]:
    """One row per (program, instruction) of the newest xplane under
    ``trace_dir``: ``Program``, ``HLO op name``, ``Framework op name``,
    ``Total self time (us)`` — the columns ``phase_times`` and
    ``scripts/roofline_attrib.py`` read. ``texts`` default to the
    ``*.hlo.txt`` files under ``trace_dir``."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir!r} "
                                "(did the profile window run?)")
    if texts is None:
        texts = {}
        for path in sorted(glob.glob(os.path.join(trace_dir, "**",
                                                  "*.hlo.txt"),
                                     recursive=True)):
            with open(path) as f:
                texts[os.path.basename(path)[:-len(".hlo.txt")]] = f.read()
    if not texts:
        raise FileNotFoundError(
            f"no program text (*.hlo.txt) under {trace_dir!r}: without "
            "it the trace's operations carry no scope")
    table = device_time_by_scope(paths[-1], texts, phase_of)
    return [{"Program": label, "HLO op name": name,
             "Framework op name": path, "Total self time (us)": 1e6 * s}
            for label, prog in table.items()
            for (name, path), s in prog["ops"].items()]


def phase_times(rows: List[dict]) -> Dict[str, Dict[str, float]]:
    """Group measured device self time by training phase.

    -> {phase: {"us": total self time, "pct": share of profiled
    time}}, phases ordered by time. Rows without a framework op name
    (infeed, runtime gaps) land in 'other'.
    """
    by_phase: Dict[str, float] = {}
    for r in rows:
        try:
            t = float(r.get("Total self time (us)") or 0.0)
        except (TypeError, ValueError):
            t = 0.0
        if not t:
            continue
        ph = phase_of(r.get("Framework op name") or "")
        by_phase[ph] = by_phase.get(ph, 0.0) + t
    total = sum(by_phase.values()) or 1.0
    return {ph: {"us": round(us, 1), "pct": round(100.0 * us / total, 2)}
            for ph, us in sorted(by_phase.items(), key=lambda kv: -kv[1])}
