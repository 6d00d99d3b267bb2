"""Device idle share, %: one less the union of the device's operation
intervals over the traced segment (profiler trace). For the backlog cells."""


def read(run):
    if run.reduction is None:
        return None
    return 100.0 * run.reduction.idle_share
