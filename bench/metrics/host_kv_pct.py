"""Host time in the engine's ``kv`` phase, % of the window: reserving and
growing the stage's KV pages, with their block-table writes, and any
preemption. Sum of ``StageRecord.host["kv"]`` over the window's steps (the
engine's own counter, tracing off)."""
from harness import phases


def read(run):
    return phases.host_pct(run, "kv")
