"""Seconds per product in a closed loop: the window's length (it closes at
an answer) over the products answered in it."""


def read(run):
    if run.loop != "closed" or not run.answered:
        return None
    return run.window_s / len(run.answered)
