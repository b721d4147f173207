"""The whole step's share of the int8 peak: 2 x live MACs per image
(``work.py``) x images per second of the traced window, over the
peak."""


def read(run):
    if run.trace is None:
        return None
    return (100.0 * run.work["ops"] * run.images / run.window_s
            / run.peaks.int8_ops_per_s)
