"""Share of slot-seconds holding a request, %: from each request's admission
(its first chunk's dispatch) to its completion, clipped to the window, over
slots x window seconds (host clock). The paper's "utilization" on the wall
clock instead of the engine's virtual one."""
from harness import stats


def read(run):
    held = 0.0
    for rec in run.reqs.values():
        if rec.admit_step is None:
            continue
        a = max(stats.dispatch_time(run, rec.admit_step), run.ws)
        b = min(rec.done if rec.done is not None else run.end, run.we)
        held += max(b - a, 0.0)
    return 100.0 * held / (run.setup.n_slots * run.window_s)
