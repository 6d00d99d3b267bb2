"""Eager device writes the KV pool dispatched per step in the window
(block-table rows on page growth, two per released slot, page copies):
``StageRecord.pool_writes`` summed over the window's steps, over their
number (the engine's own counter)."""
from harness import phases


def read(run):
    return phases.pool_writes_per_step(run)
