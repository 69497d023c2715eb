"""Phase-scoped tracing: named profiler annotations + wall-time phase timers
+ the opt-in mid-run JAX profiler capture window.

:class:`phase` is the one instrumentation primitive the loop uses: it opens
a ``jax.profiler.TraceAnnotation`` (so the phase shows up as a named span in
a captured trace — dispatch, sign gather, epoch reorder, loader wait,
checkpoint save) *and* records the wall duration into the registry's
streaming-quantile timer under ``phase.<name>``. Timing is
``time.perf_counter`` on the host — it measures dispatch/host time, never
forces a device sync.

The spans the training loop opens (``train/loop.py``): ``loader_wait`` and
``dispatch`` per step, ``ckpt_save``, and at a GraB epoch boundary
``epoch_reorder`` with ``sign_fetch``, ``reorder`` and ``rollover`` nested
in it, then ``epoch_hook`` around the caller's hook. The loop's
``phase.step`` timer is a plain timer over a whole iteration: on the async
path it times the enqueue, not the device's step. What runs on the device
is named by ``jax.named_scope`` instead, in each HLO instruction's
``op_name``: ``fwd_bwd``, ``grab_balance``, ``grad_accum`` and
``optimizer`` in the step (``train/step.py``), ``grab_rollover`` in the
epoch-end rollover. A reader of a device trace attributes an op to the
first of these names in its ``op_name``; the names are a contract with
such readers, and changing one changes what they measure.

:class:`ProfileWindow` implements ``--profile-steps A:B``: the run captures
a JAX profiler trace exactly for global steps ``[A, B)`` and writes it to
``log_dir`` (view with TensorBoard or Perfetto). Capturing mid-run, after
compilation and warm-up, is the only way to see steady-state overlap —
a trace from step 0 is all compile time.
"""
from __future__ import annotations

import time
from typing import Optional, Tuple

import jax

from repro.obs.registry import MetricsRegistry


class phase:
    """Context manager: profiler-annotated, registry-timed phase scope.

    >>> with phase("dispatch", reg):
    ...     state, metrics = step_fn(state, batch)

    records into ``reg.timer("phase.dispatch")`` and annotates the span for
    any active profiler trace. ``reg=None`` keeps the annotation only.
    """

    __slots__ = ("name", "reg", "_t0", "_ann")

    def __init__(self, name: str, reg: Optional[MetricsRegistry] = None):
        self.name = name
        self.reg = reg
        self._ann = None

    def __enter__(self):
        try:
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        except Exception:          # profiler backend unavailable: time only
            self._ann = None
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dt = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        if self.reg is not None:
            self.reg.timer(f"phase.{self.name}").record(dt)
        return False


def parse_profile_steps(spec: Optional[str]) -> Optional[Tuple[int, int]]:
    """``"A:B"`` -> ``(A, B)`` with ``0 <= A < B``; None/"" -> None."""
    if not spec:
        return None
    try:
        a_s, b_s = str(spec).split(":")
        a, b = int(a_s), int(b_s)
    except ValueError:
        raise ValueError(
            f"--profile-steps wants 'A:B' (capture global steps [A, B)), "
            f"got {spec!r}") from None
    if not (0 <= a < b):
        raise ValueError(f"--profile-steps window must have 0 <= A < B, "
                         f"got {a}:{b}")
    return a, b


class ProfileWindow:
    """Capture a JAX profiler trace for global steps ``[start, stop)``.

    Drive it with :meth:`on_step` once per step *before* dispatching that
    step, and :meth:`close` when the run ends (stops a still-open capture if
    the run finished inside the window). Inactive (``spec=None``) instances
    are free no-ops, so the loop calls unconditionally.
    """

    def __init__(self, spec: Optional[str], log_dir: str = "profile_trace",
                 reg: Optional[MetricsRegistry] = None):
        self.window = parse_profile_steps(spec)
        self.log_dir = log_dir
        self.reg = reg
        self.active = False

    def on_step(self, global_step: int) -> None:
        if self.window is None:
            return
        start, stop = self.window
        if not self.active and start <= global_step < stop:
            jax.profiler.start_trace(self.log_dir)
            self.active = True
            if self.reg is not None:
                self.reg.event(f"[obs] profiler trace started at step "
                               f"{global_step} -> {self.log_dir}")
        elif self.active and global_step >= stop:
            self._stop(global_step)

    def close(self) -> None:
        if self.active:
            self._stop(None)

    def _stop(self, global_step) -> None:
        jax.profiler.stop_trace()
        self.active = False
        if self.reg is not None:
            at = ("at run end" if global_step is None
                  else f"at step {global_step}")
            self.reg.event(f"[obs] profiler trace stopped {at}; inspect "
                           f"{self.log_dir} with tensorboard/perfetto")
