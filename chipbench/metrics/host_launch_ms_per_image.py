"""Host self time of the program's ``nc.pallas.launch`` spans (the
``pallas`` adapter's envelope check, flatten, row padding and enqueue),
per image (``program_spans.py``); nothing where the program has no such
span."""

from chipbench.program_spans import stage_ms_per_image


def read(run):
    return stage_ms_per_image(run, "launch")
