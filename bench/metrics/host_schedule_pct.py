"""Host time in the engine's ``schedule`` phase, % of the window: the
scheduler's proposal, admission against the page pool, the iteration
policy's decision and the round's plan. Sum of ``StageRecord.host["schedule"]``
over the window's steps (the engine's own counter, tracing off)."""
from harness import phases


def read(run):
    return phases.host_pct(run, "schedule")
