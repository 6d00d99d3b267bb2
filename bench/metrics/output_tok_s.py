"""Output tokens per second: every output token that reached the host
inside the window, over the window's seconds (host clock, tracing off)."""


def read(run):
    n = sum(
        k for rec in run.reqs.values() for t, k in rec.deliveries if run.in_window(t)
    )
    return n / run.window_s
