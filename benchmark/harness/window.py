"""Open and close the measured window around a call that has no clock.

``run_simulation`` counts rounds, not seconds. The window opens when the
checkpoint of the last followed (set-up) round lands on disk, and closes
``seconds`` later with the program's own graceful stop: a SIGTERM sets
its flag, the round in flight finishes and the call returns. Rounds are
counted, and timed, by the program's own per-round clock; this thread
only decides when to stop.

A ``tail`` is something that watches the end of the window (a profiler
session): ``(lead_seconds, start, finish)``. ``start`` is called
``lead_seconds`` before the close (a window shorter than that is
lengthened to it) and ``finish`` after the stop has been sent, so
whatever ``finish`` takes (writing a trace out) is in no window.
"""

from __future__ import annotations

import os
import signal
import threading
import time


class Window:
    def __init__(self, opens_when_exists: str, seconds: float,
                 tail=None, deadline_s: float = 1500.0):
        self._path = opens_when_exists
        self._seconds = seconds
        self._tail = tail
        self._deadline = deadline_s
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._watch, name="bench-window", daemon=True
        )
        self.opened_at: float | None = None  # time.perf_counter()
        self.signalled = False
        self.error: BaseException | None = None

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        # A tail's ``finish`` may still be writing (tens of seconds for a
        # trace of four chips); a run has 360 s in all.
        self._thread.join(timeout=300.0)
        if self._thread.is_alive():
            raise RuntimeError("the window's watcher thread did not end")

    def _watch(self):
        try:
            t0 = time.perf_counter()
            while not os.path.exists(self._path):
                if self._stop.wait(0.002):
                    return
                if time.perf_counter() - t0 > self._deadline:
                    raise TimeoutError(
                        f"{self._path} did not appear in {self._deadline} s"
                    )
            self.opened_at = time.perf_counter()
            lead, start, finish = self._tail or (0.0, None, None)
            if self._stop.wait(max(self._seconds - lead, 0.0)):
                return
            if start is not None:
                start()
            try:
                stopped = self._stop.wait(lead)
                if not stopped:
                    self.signalled = True
                    os.kill(os.getpid(), signal.SIGTERM)
            finally:
                if finish is not None:
                    finish()
        except BaseException as e:  # surfaced by the main thread
            self.error = e
            os.kill(os.getpid(), signal.SIGTERM)
