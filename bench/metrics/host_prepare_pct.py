"""Host time in the engine's ``prepare`` phase, % of the window: building
the stage's inputs on the host before the dispatch. Sum of
``StageRecord.host["prepare"]`` over the window's steps (the engine's own
counter, tracing off)."""
from harness import phases


def read(run):
    return phases.host_pct(run, "prepare")
