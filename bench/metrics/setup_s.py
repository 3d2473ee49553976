"""Seconds from process start to the window's start: imports, device
start, operand pools, compile or cache load, warm-up."""


def read(run):
    return run.setup_s
