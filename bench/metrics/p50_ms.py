"""Median latency of every answered request of the window, from its
scheduled send time to the receipt of its reply at the client."""


def read(run):
    return run.pct(50)
