"""Images completed in the window over the window's length (host clock)."""


def read(run):
    return run.completed / run.window_s
