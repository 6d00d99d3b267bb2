"""Set-up seconds: process start to the window's start -- weights, the
slot and page fit, warming every serving shape, and the traffic's own
warm-up (a backlog until every slot was admitted once; an open mix its
warm-up seconds)."""


def read(run):
    return run.setup_s
