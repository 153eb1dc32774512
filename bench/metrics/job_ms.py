"""An application's whole time: the window, closed at a job boundary, over
the jobs completed in it (host clock)."""


def read(run):
    if run.window is None:
        return None
    return run.window.seconds / run.window.jobs * 1e3
