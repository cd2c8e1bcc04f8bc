"""Headers verified over the window: every header of each replay inside
it, over the time from the end of the replay that opened the window to
the end of the one that closed it."""


def read(run):
    return run.headers / run.window_s if run.replays else None
