"""Fused decode rounds per fused decode dispatch in the window: the mean
horizon K the engine chose (1 means every round paid a dispatch and a host
sync)."""
from harness import stats


def read(run):
    idx = stats.window_stage_indices(run, "decode")
    if not idx:
        return None
    return sum(run.steps[i].stage.rounds for i in idx) / len(idx)
