"""Seconds from process start to the window's start, compilation and
warm-up included."""


def read(run):
    return run.setup_s
