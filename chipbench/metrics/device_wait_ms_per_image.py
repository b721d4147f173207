"""Host self time of the program's ``nc.pallas.wait`` spans: the host
blocked on each tile's device run and its copy back, per image
(``program_spans.py``); nothing where the program has no such span."""

from chipbench.program_spans import stage_ms_per_image


def read(run):
    return stage_ms_per_image(run, "wait")
