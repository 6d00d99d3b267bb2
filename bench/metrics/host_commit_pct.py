"""Host time in the engine's ``commit`` phase, % of the window: everything
after the stage's results are on the host (tokens, the stage record,
finished prefills, releases of finished slots). Sum of
``StageRecord.host["commit"]`` over the window's steps (the engine's own
counter, tracing off)."""
from harness import phases


def read(run):
    return phases.host_pct(run, "commit")
