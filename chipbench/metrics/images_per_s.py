"""Images answered per second over the whole window: every image
completed in it, over the window's length on the host clock."""


def read(run):
    return run.images / run.window_s
