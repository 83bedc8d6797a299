"""Named spans of the serving engine's step, and the collector's pauses.

``span(stats, name, step)`` wraps one phase of ``ServingEngine.stream_step``.
It opens ``jax.profiler.TraceAnnotation("engine.<name>")`` with the step
index as metadata, so a profiler trace names the phase, and on exit adds
the phase's ``time.perf_counter`` seconds to ``EngineStats.<name>_s``, so
the engine's counters hold the same time with no trace running. There is
no other store and no exporter. With the profiler off an annotation costs
well under a microsecond.

``GC`` is the process's one ``gc.callbacks`` hook, installed while any
engine has a stream session open: each collector pause becomes one
``python.gc`` annotation and is added once to ``GC.pause_s``, however many
engines are open.
"""
from __future__ import annotations

import gc
import threading
import time

from jax.profiler import TraceAnnotation


class span:
    """Context manager timing one phase of the engine's step."""

    __slots__ = ("stats", "field", "note", "t0")

    def __init__(self, stats, name: str, step: int):
        self.stats = stats
        self.field = name + "_s"
        self.note = TraceAnnotation("engine." + name, step=step)

    def __enter__(self):
        self.note.__enter__()
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        setattr(self.stats, self.field, getattr(self.stats, self.field) + dt)
        self.note.__exit__(*exc)


class GcPauses:
    """A ``gc.callbacks`` hook held by the open stream sessions: installed by
    the first ``acquire``, removed by the last ``release``. ``pause_s`` is
    the process's collector seconds while it was installed."""

    def __init__(self):
        self.users = 0
        self.pause_s = 0.0
        self._lock = threading.Lock()
        self._open = None  # (annotation, start) of the running collection

    def acquire(self) -> None:
        with self._lock:
            if self.users == 0:
                gc.callbacks.append(self)
            self.users += 1

    def release(self) -> None:
        with self._lock:
            self.users -= 1
            if self.users == 0:
                gc.callbacks.remove(self)

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            note = TraceAnnotation("python.gc",
                                   generation=info.get("generation", -1))
            note.__enter__()
            self._open = (note, time.perf_counter())
        elif self._open is not None:
            note, t0 = self._open
            self._open = None
            self.pause_s += time.perf_counter() - t0
            note.__exit__(None, None, None)


GC = GcPauses()
