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

import jax

# Reusable no-op span for the disabled path (nullcontext is documented
# reentrant and reusable — nothing allocated per use).
NULL_SPAN = contextlib.nullcontext()


def span(name: str):
    """Host-side labeled region for xprof (nests freely)."""
    return jax.profiler.TraceAnnotation(name)


def step_span(step: int, name: str = "train"):
    """Per-step region; xprof's step-oriented views key on these."""
    return jax.profiler.StepTraceAnnotation(name, step_num=step)


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
