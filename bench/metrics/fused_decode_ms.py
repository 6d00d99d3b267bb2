"""Device time of one fused decode round, ms: the seconds of the programs
whose name holds ``fused_decode`` in the profile (the profiler's module
events), over the fused rounds of the decode stages the profile holds.
Unlike ``decode_round_ms`` it picks the program by name, so the KV pool's
eager writes dispatched in the same steps are not in it. The profile's
window closes after the last step it holds, which may end past
``run.traced[1]``, so the rounds are counted up to the window's end. None
without a module thread in the trace or without a program of that name."""


def read(run):
    red = run.reduction
    if red is None or not red.runs:
        return None
    secs = sum(s for name, (_, s) in red.programs.items() if "fused_decode" in name)
    start = run.traced[0]
    end = start + red.window_s
    rounds = sum(
        s.stage.rounds for s in run.steps
        if start < s.t1 <= end and s.stage.kind.value == "decode"
    )
    if not rounds or secs <= 0:
        return None
    return 1e3 * secs / rounds
