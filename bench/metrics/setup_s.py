"""Set-up time: process start to the first scheduled request of the
window (loading, weights, corpus, engine build, warm-up and compiles)."""


def read(run):
    return run.setup_s
