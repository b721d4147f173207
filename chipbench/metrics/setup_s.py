"""Process start to the first timed request: device check, weights from
the seed, engine build and the warm-up batch."""


def read(run):
    return run.setup_s
