"""Device time of one fused decode round, ms: the device seconds of the
programs that ran inside the traced segment's fused decode stages (profiler trace,
each program run attributed to the harness step it ran in), over the fused
rounds those stages ran."""
from harness import stats


def read(run):
    if run.reduction is None:
        return None
    rounds = sum(run.steps[i].stage.rounds for i in stats.traced_stage_indices(run, "decode"))
    secs = stats.stage_device_seconds(run, "decode")
    if not rounds or secs <= 0:
        return None
    return 1e3 * secs / rounds
