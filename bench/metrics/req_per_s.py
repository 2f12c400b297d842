"""Replies received inside the window, over the window's length."""


def read(run):
    return run.replies_in_window / run.seconds
