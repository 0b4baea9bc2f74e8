"""Whole-process pauses, seen from inside the process.

``Watch`` records every cyclic garbage collection (its generation, start
and length, from ``gc.callbacks``) and, from a thread that sleeps
``tick_s`` at a time, every stretch longer than ``stall_s`` in which that
thread could not run. A pause that holds the interpreter lock stops the
load's sender and receiver and the server's dispatcher alike, so it shows
in every request that falls into it; ``summary`` says how many fell into
the window and how long the longest was. With ``dump_s``, a pause longer
than that also writes every thread's stack to standard error, from
``faulthandler``'s own thread, which needs no interpreter lock: the thread
that holds the lock shows the call it is in. Re-arming that timer starts a
thread and joins the last one, under the lock, every ``dump_s / 4``: use it
to find a pause, not in a measured run.
"""
from __future__ import annotations

import faulthandler
import gc
import sys
import threading
import time


class Watch:
    def __init__(self, tick_s: float = 0.01, stall_s: float = 0.05,
                 dump_s: float = 0.0):
        self.tick_s, self.stall_s, self.dump_s = tick_s, stall_s, dump_s
        self.collections: list = []     # (gen, start, seconds)
        self.stalls: list = []          # (start, seconds)
        self._gc_t0 = 0.0
        self._stop = threading.Event()
        gc.callbacks.append(self._gc)
        self._thread = threading.Thread(target=self._beat, daemon=True,
                                        name="bench-stalls")
        self._thread.start()

    def _gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            t = time.perf_counter()
            self.collections.append((info["generation"], self._gc_t0,
                                     t - self._gc_t0))

    def _beat(self) -> None:
        last = armed = time.perf_counter()
        while not self._stop.wait(self.tick_s):
            now = time.perf_counter()
            if self.dump_s and now - armed > self.dump_s / 4:
                faulthandler.dump_traceback_later(self.dump_s,
                                                  file=sys.stderr)
                armed = now
            if now - last > self.stall_s + self.tick_s:
                self.stalls.append((last, now - last - self.tick_s))
            last = now

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()
        if self.dump_s:
            faulthandler.cancel_dump_traceback_later()
        if self._gc in gc.callbacks:
            gc.callbacks.remove(self._gc)

    def summary(self, lo: float, hi: float) -> str:
        """One line on the pauses that began in [lo, hi)."""
        cols = [c for c in self.collections if lo <= c[1] < hi]
        by_gen = [sum(1 for c in cols if c[0] == g) for g in range(3)]
        worst = max(cols, key=lambda c: c[2], default=None)
        stalls = [s for s in self.stalls if lo <= s[0] < hi]
        long = max(stalls, key=lambda s: s[1], default=None)
        out = (f"collections by generation {by_gen}, longest "
               + (f"{1e3 * worst[2]:.1f} ms (gen {worst[0]}, at "
                  f"+{worst[1] - lo:.2f} s)" if worst else "none")
               + f"; {len(stalls)} pauses over {1e3 * self.stall_s:.0f} ms"
               + (f", longest {1e3 * long[1]:.1f} ms at +{long[0] - lo:.2f} s"
                  if long else ""))
        return out
