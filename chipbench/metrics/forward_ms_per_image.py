"""Forward wall time (the span around ``engine._forward``) per image
served in the window."""


def read(run):
    return 1e3 * run.forward_s / run.images
