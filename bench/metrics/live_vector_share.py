"""User tower: the share of the interest vectors the window's launches
scanned that carried a live interest (MIND's K'_u of each row's K), in
percent: the change in ``QueryFrontend.stats`` live_vectors over
scan_vectors.  A program without the counters reads nothing."""


def read(run):
    scan = run.frontend.get("scan_vectors", 0)
    if scan == 0:
        return None
    return 100.0 * run.frontend.get("live_vectors", 0) / scan
