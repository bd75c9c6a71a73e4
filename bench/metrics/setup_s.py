"""Seconds from process start to the window's start: loading, data,
warm-up and, in a run that compiles, compilation (host clock)."""


def read(run):
    return run.setup_s
