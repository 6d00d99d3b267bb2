"""Wall-clock phases of one engine step, on the profiler's clock too.

``Engine.serve_step`` splits every step that runs a stage into consecutive
phases (``schedule``, ``kv``, ``prepare``, ``launch``, ``wait``,
``commit``). :class:`Phases` times them with ``time.perf_counter`` and
opens a ``jax.profiler.TraceAnnotation`` named ``engine.<phase>`` for each,
so a profile shows what the host was doing in every gap between device
programs. It is always on and is not an :class:`~repro.obs.Observation`
callback: a phase costs one ``perf_counter`` read and one ``TraceMe``.
"""
from __future__ import annotations

import time
from typing import Dict, Optional

from jax.profiler import TraceAnnotation


class Phases:
    """Accumulates seconds per phase between ``start()`` and ``stop()``.

    ``mark(name)`` closes the open phase and opens ``name``; marking a phase
    again later adds to what it already holds, and marking the phase that is
    open does nothing. ``mark`` outside ``start``/``stop`` does nothing, so
    the stage runners it is called from also work outside a serve step.
    ``t`` lets a caller reuse a ``perf_counter`` reading it took itself, so
    a phase boundary and the caller's own timing agree exactly.
    """

    __slots__ = ("_seconds", "_name", "_t", "_span", "_running")

    def __init__(self) -> None:
        self._seconds: Dict[str, float] = {}
        self._name: Optional[str] = None
        self._t = 0.0
        self._span: Optional[TraceAnnotation] = None
        self._running = False

    def start(self) -> None:
        self._seconds = {}
        self._name = None
        self._t = time.perf_counter()
        self._running = True

    def mark(self, name: str, t: Optional[float] = None) -> None:
        if not self._running or name == self._name:
            return
        now = time.perf_counter() if t is None else t
        self._close(now)
        self._name = name
        self._t = now
        self._span = TraceAnnotation("engine." + name)
        self._span.__enter__()

    def stop(self) -> Dict[str, float]:
        """Close the open phase; returns seconds per phase marked."""
        if self._running:
            self._close(time.perf_counter())
            self._running = False
        return self._seconds

    def _close(self, now: float) -> None:
        if self._name is not None:
            self._seconds[self._name] = (
                self._seconds.get(self._name, 0.0) + now - self._t
            )
            self._span.__exit__(None, None, None)
            self._name = self._span = None
