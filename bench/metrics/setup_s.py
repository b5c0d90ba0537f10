"""setup_s: process start to the first request of the window (weights,
plan, compile, warm-up and the traffic's own warm-up), host clock."""


def read(run):
    return run.setup_s
