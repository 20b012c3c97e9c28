"""Background checkpoint writer: double-buffered, off the critical path.

The save path splits in two (docs/checkpoint.md): the BLOCKING part is
only the device→host snapshot at the step boundary (plus a queue put);
serialization, checksumming, and the atomic commit run on this thread.
Double buffering bounds host memory: at most TWO snapshots exist at once
— one being written, one queued. A third ``submit`` blocks until the
writer drains (that wait is the backpressure a trainer sees as a slow
``save`` when saves outpace the disk).

A failed write never kills the training process mid-step: the exception
is captured and re-raised on the NEXT ``submit``/``drain`` (the reference
posture — a checkpoint subsystem must fail loudly but at a boundary the
trainer can handle).
"""

from __future__ import annotations

import logging
import queue
import threading
import time
import weakref
from typing import Callable, Optional

log = logging.getLogger("horovod_tpu.checkpoint")

# Every live AsyncWriter, so a signal handler can quiesce in-flight
# commits process-wide without plumbing writer references through the
# monitor layer (flight.py drains here before dumping on SIGTERM — a
# torn half-written commit is exactly what the manifest-last protocol
# exists to prevent, and re-delivering the signal mid-write would
# waste the window the preemption grace period grants us).
_live_writers: "weakref.WeakSet[AsyncWriter]" = weakref.WeakSet()


class AsyncWriter:
    """One daemon thread draining a bounded job queue.

    ``submit(job)`` enqueues a zero-argument callable; ``maxsize=1`` plus
    the job in flight gives the double buffer. ``drain()`` blocks until
    every submitted job has finished (the kill-before-commit windows of
    the smoke test live between ``submit`` and ``drain``).

    Idle-tracking is a pending-job counter guarded by one condition
    variable: ``submit`` increments BEFORE enqueueing and the worker
    decrements AFTER the job (and any captured error) lands, so a
    ``drain`` can never observe "idle" while a submitted job is still in
    flight (an Event set from a stale emptiness check could).
    """

    def __init__(self, name: str = "hvd-ckpt-writer") -> None:
        self._queue: "queue.Queue" = queue.Queue(maxsize=1)
        self._cond = threading.Condition()
        self._pending = 0
        self._error: Optional[BaseException] = None
        self._closed = False
        self._thread = threading.Thread(target=self._loop, name=name,
                                        daemon=True)
        self._thread.start()
        _live_writers.add(self)

    def _loop(self) -> None:
        while True:
            job = self._queue.get()
            if job is None:
                return
            try:
                job()
            except BaseException as e:  # surfaced on next submit/drain
                log.error("async checkpoint write failed: %s", e)
                with self._cond:
                    self._error = e
            finally:
                with self._cond:
                    self._pending -= 1
                    if self._pending == 0:
                        self._cond.notify_all()

    def raise_pending(self) -> None:
        """Re-raise (once) an error captured on the writer thread."""
        with self._cond:
            err, self._error = self._error, None
        if err is not None:
            raise err

    def submit(self, job: Callable[[], None]) -> None:
        """Enqueue a write job; blocks only when two snapshots are
        already in flight (the double-buffer backpressure)."""
        if self._closed:
            raise RuntimeError("AsyncWriter is closed")
        self.raise_pending()
        with self._cond:
            self._pending += 1
        # Outside the lock: a full queue blocks here until the worker
        # frees a slot, and the worker's decrement needs the lock.
        self._queue.put(job)

    @property
    def busy(self) -> bool:
        with self._cond:
            return self._pending > 0

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Wait for all submitted jobs; True when idle (False = timeout).
        Re-raises a captured writer error."""
        with self._cond:
            done = self._cond.wait_for(lambda: self._pending == 0, timeout)
        self.raise_pending()
        return done

    def close(self, timeout: float = 60.0) -> None:
        """Drain and stop the thread (idempotent)."""
        if self._closed:
            return
        self._closed = True
        self._queue.put(None)
        self._thread.join(timeout)
        self.raise_pending()


def drain_all(timeout: float = 10.0) -> bool:
    """Drain every live AsyncWriter under one shared deadline.

    Signal-handler safe: never raises (captured writer errors stay
    captured for the owner's next ``submit``/``drain`` to surface) and
    never waits past ``timeout`` in total, however many writers exist.
    Returns True when every writer went idle within the budget.
    """
    deadline = time.monotonic() + max(0.0, timeout)
    all_idle = True
    for writer in list(_live_writers):
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            all_idle = all_idle and not writer.busy
            continue
        try:
            with writer._cond:
                idle = writer._cond.wait_for(
                    lambda: writer._pending == 0, remaining)
        except Exception:
            idle = False
        all_idle = all_idle and idle
    return all_idle
