"""Trace spans and windowed profiling.

Spans wrap ``jax.profiler.TraceAnnotation`` / ``StepTraceAnnotation``
so the step, data-wait, eval, and checkpoint phases show up as labeled
regions in xprof alongside the device timeline. ``WindowedProfiler``
replaces the old whole-run ``jax.profiler.start_trace`` toggle
(tpunet/main.py pre-obs): a trace is captured for exactly the
configured step window [start, start+num), with ``block_until_ready``
fences at the two window edges ONLY — async dispatch means work queued
before the window would otherwise bleed into it, and work dispatched
inside the window would escape it.
"""

from __future__ import annotations

import contextlib
import glob
import os
import time

import jax

# Reusable no-op span for the disabled path (nullcontext is documented
# reentrant and reusable — nothing allocated per use).
NULL_SPAN = contextlib.nullcontext()


def span(name: str, **args):
    """Host-side labeled region for xprof (nests freely); ``args``
    become the event's arguments, encoded only while a trace is on."""
    return jax.profiler.TraceAnnotation(name, **args)


def step_span(step: int, name: str = "train"):
    """Per-step region; xprof's step-oriented views key on these."""
    return jax.profiler.StepTraceAnnotation(name, step_num=step)


class PhaseTotal:
    """What the closed spans of one name add up to."""

    __slots__ = ("seconds", "count", "longest")

    def __init__(self):
        self.seconds = 0.0             # self time, summed
        self.count = 0
        self.longest = 0.0             # longest single span (self time)
        #                                since its reader last reset it


class PhaseClock:
    """Where one thread's spans add up their host seconds, by name.

    ``totals[name]`` sums the spans of that name that have closed, in
    SELF time: what a span's children (spans opened inside it under the
    same clock) took goes to their own names, so the names partition
    the thread's time under any span. Plain floats written by the one
    thread that opens the spans: no lock, nothing reaches a registry
    until its owner copies them out (``Engine._emit_record``)."""

    __slots__ = ("totals", "open")

    def __init__(self):
        self.totals = {}               # {span name: PhaseTotal}
        self.open = None               # the innermost span still open


class Span:
    """One host trace span: ``inner`` (a ``TraceAnnotation`` or
    ``StepTraceAnnotation``: the profiler's clock, which the device's
    operations share) that can also drop begin/end events into a
    flight-recorder ring (``rec.record``: the crash tail's "which
    phase were we in", the timeline's device phases) and add its
    ``perf_counter`` seconds to a ``PhaseClock``. The trainer's spans
    (``Observability.span``) and the serve engine's phases
    (``Engine._phase``) are both this class. The end lands in the ring
    even when the body raised, so a failing device call leaves no
    open span for the timeline to stretch to the end of the
    recording."""

    __slots__ = ("_inner", "_name", "_rec", "_clock", "_parent", "_t0",
                 "_child_s")

    def __init__(self, inner, name: str, rec=None, clock=None):
        self._inner = inner
        self._name = name
        self._rec = rec
        self._clock = clock

    def __enter__(self):
        if self._rec is not None:
            self._rec.record("span", self._name)
        clock = self._clock
        if clock is not None:
            self._parent = clock.open
            clock.open = self
            self._child_s = 0.0
            self._t0 = time.perf_counter()
        self._inner.__enter__()
        return self

    def __exit__(self, *exc):
        try:
            return self._inner.__exit__(*exc)
        finally:
            clock = self._clock
            if clock is not None:
                elapsed = time.perf_counter() - self._t0
                own = elapsed - self._child_s
                total = clock.totals.get(self._name)
                if total is None:
                    total = clock.totals[self._name] = PhaseTotal()
                total.seconds += own
                total.count += 1
                if own > total.longest:
                    total.longest = own
                parent = clock.open = self._parent
                if parent is not None:
                    parent._child_s += elapsed
            if self._rec is not None:
                self._rec.record("span_end", self._name)


class WindowedProfiler:
    """Capture a jax profiler trace for steps [start, start+num).

    ``num_steps == 0`` with a non-empty ``profile_dir`` keeps the old
    whole-run semantics (start at the first step, stop at ``close()``)
    so existing ``--profile-dir`` invocations still work. ``on_step``
    is called before each step's dispatch with the global step number
    and a ``sync`` callable (``block_until_ready`` over the live
    state); the sync runs at window edges only, never on interior
    steps.
    """

    def __init__(self, profile_dir: str, start_step: int = 0,
                 num_steps: int = 0):
        if start_step < 0 or num_steps < 0:
            raise ValueError(
                f"profile window must be non-negative, got start_step="
                f"{start_step} num_steps={num_steps}")
        self.profile_dir = profile_dir
        self.start_step = start_step
        self.num_steps = num_steps
        self.running = False
        self._done = not bool(profile_dir)

    @property
    def active(self) -> bool:
        """True while this profiler may still start or stop a trace
        (the loop skips the per-step check entirely once False)."""
        return not self._done or self.running

    def on_step(self, step: int, sync=None) -> None:
        if self._done and not self.running:
            return
        if self.running:
            if (self.num_steps
                    and step >= self.start_step + self.num_steps):
                self._stop(sync)
            return
        if step >= self.start_step:
            if self.num_steps and step >= self.start_step + self.num_steps:
                # The run resumed past the window (or the window fell
                # inside a skipped epoch): never trace.
                self._done = True
                return
            if sync is not None:
                sync()  # fence: pre-window dispatches complete outside
            jax.profiler.start_trace(self.profile_dir)
            self.running = True

    def _stop(self, sync=None) -> None:
        if sync is not None:
            sync()  # fence: in-window dispatches complete inside
        jax.profiler.stop_trace()
        self.running = False
        self._done = True

    def close(self, sync=None) -> None:
        """End-of-run: flush a still-open (whole-run or truncated)
        window."""
        if self.running:
            self._stop(sync)
        self._done = True
        if self.profile_dir and glob.glob(os.path.join(
                self.profile_dir, "**", "*.xplane.pb"), recursive=True):
            # The programs' texts beside the trace, so that
            # scripts/obs_report.py --trace can name its operations
            # (tpunet/obs/device_time.py). End of run, off every step.
            try:
                from tpunet.obs import device_time
                device_time.write_program_texts(self.profile_dir)
            except Exception as e:  # noqa: BLE001 — never stop a run
                print(f"profile: program texts not written: {e}",
                      flush=True)
