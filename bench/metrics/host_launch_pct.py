"""Host time in the engine's ``launch`` phase, % of the window: from the
stage's dispatch until the jitted call returns (host-to-device copies and
the dispatch of the argument tree). It lies inside the stage's
dispatch-to-results time, so the device may sit idle in it. Sum of
``StageRecord.host["launch"]`` over the window's steps (the engine's own
counter, tracing off)."""
from harness import phases


def read(run):
    return phases.host_pct(run, "launch")
