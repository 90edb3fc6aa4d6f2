"""Seconds from process start to the first request of the window: index
load (or build), upload, warm-up from the compile cache, traffic."""


def read(run):
    return run.setup_s
