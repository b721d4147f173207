"""Device time of the ops other than the kernel inside the ``pallas``
backend's adapter program (the word-grid decode), per image."""


def read(run):
    s = run.trace
    return 1e3 * s.decode_s / run.images if s and s.decode_s else None
