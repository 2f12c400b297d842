"""Frontend layer: requests completed per micro-batch dispatch over the
window (the change in ``QueryFrontend.stats`` completed / dispatches)."""


def read(run):
    if run.frontend["dispatches"] == 0:
        return None
    return run.frontend["completed"] / run.frontend["dispatches"]
