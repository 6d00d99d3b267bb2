"""Host gap inside ``serve_step``, %: of the window, the time the harness
spent inside ``serve_step`` with no stage between its dispatch and its
results on the host (scheduling, bookkeeping, input preparation), read from
the harness's timestamps around each step and the stage's own
dispatch-to-results time. For the backlog cells (moves ``output_tok_s``)."""


def read(run):
    steps = run.window_steps()
    if not steps:
        return None
    host = sum(s.t1 - s.t0 - s.stage.duration for s in steps)
    return 100.0 * host / run.window_s
