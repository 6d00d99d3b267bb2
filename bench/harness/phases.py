"""Readings of the engine's own per-step records: the wall seconds of each
phase of a serve step (``StageRecord.host``) and the KV pool's eager device
writes (``StageRecord.pool_writes``). A program whose records lack them
reads as None."""
from __future__ import annotations

from typing import Optional


def host_pct(run, phase: str) -> Optional[float]:
    """Seconds the window's steps spent in ``phase``, % of the window."""
    hosts = [getattr(s.stage, "host", None) for s in run.window_steps()]
    if not hosts or not all(hosts):
        return None
    return 100.0 * sum(h.get(phase, 0.0) for h in hosts) / run.window_s


def pool_writes_per_step(run) -> Optional[float]:
    steps = run.window_steps()
    if not steps or not all(hasattr(s.stage, "pool_writes") for s in steps):
        return None
    return sum(s.stage.pool_writes for s in steps) / len(steps)
