"""Host/device trace correlation: ``hvd.profile_window``.

The Timeline records *host-side* framework events; the platform profiler
(``jax.profiler``) records *device* activity. This module brackets the
two so one training window can be read across both traces:

* :func:`profile_window` starts a ``jax.profiler`` trace and marks the
  window on the Timeline (``PROFILE:WINDOW`` span + ``PROFILE:START``/
  ``PROFILE:STOP`` instants carrying the logdir, so a Timeline reader
  can find the matching device trace);
* :meth:`ProfileWindow.steps` yields each step inside a
  ``jax.profiler.StepTraceAnnotation`` (the device trace's step marker —
  the same annotation ``DistributedOptimizer`` and the serve engine use)
  and a ``PROFILE:STEP`` Timeline span, and feeds the host wall time of
  every step into the ``profile.step_ms`` histogram.
"""

from __future__ import annotations

import contextlib
import os
import tempfile
import time
from typing import Iterator, Optional

from . import registry as _registry


def _timeline():
    try:
        from ..common import basics

        return basics._state.timeline
    except Exception:  # pragma: no cover - interpreter teardown
        return None


class ProfileWindow:
    """Handle yielded by :func:`profile_window`."""

    def __init__(self, num_steps: int, logdir: str) -> None:
        self.num_steps = num_steps
        self.logdir = logdir
        self.step_times_ms = []

    def steps(self) -> Iterator[int]:
        """Iterate the window's steps: run exactly one training step per
        yielded index — each is device-marked (StepTraceAnnotation) and
        Timeline-bracketed (``PROFILE:STEP``)."""
        import jax

        tl = _timeline()
        hist = _registry.histogram("profile.step_ms")
        for i in range(self.num_steps):
            t0 = time.perf_counter()
            if tl is not None:
                tl.begin("profile", "PROFILE:STEP")
            try:
                with jax.profiler.StepTraceAnnotation("hvd_step",
                                                      step_num=i):
                    yield i
            finally:
                if tl is not None:
                    tl.end("profile", "PROFILE:STEP")
                dt_ms = (time.perf_counter() - t0) * 1e3
                self.step_times_ms.append(dt_ms)
                hist.observe(dt_ms)


@contextlib.contextmanager
def profile_window(num_steps: int, logdir: Optional[str] = None):
    """Bracket a ``jax.profiler`` trace with the Timeline.

    Usage::

        with hvd.profile_window(5) as win:
            for _ in win.steps():
                params, opt_state, loss = train_step(...)
        # win.logdir now holds the device trace; the Timeline carries the
        # matching PROFILE:WINDOW span and per-step PROFILE:STEP spans.

    ``logdir`` defaults to ``HOROVOD_PROFILE_DIR`` or a fresh temp dir.
    """
    import jax

    logdir = (logdir or os.environ.get("HOROVOD_PROFILE_DIR")
              or tempfile.mkdtemp(prefix="hvd-profile-"))
    tl = _timeline()
    win = ProfileWindow(num_steps, logdir)
    if tl is not None:
        tl.begin("profile", "PROFILE:WINDOW")
        tl.instant("PROFILE:START", tid="profile",
                   args={"logdir": logdir, "num_steps": num_steps})
    jax.profiler.start_trace(logdir)
    try:
        yield win
    finally:
        jax.profiler.stop_trace()
        if tl is not None:
            tl.instant("PROFILE:STOP", tid="profile",
                       args={"logdir": logdir,
                             "steps_run": len(win.step_times_ms)})
            tl.end("profile", "PROFILE:WINDOW")
