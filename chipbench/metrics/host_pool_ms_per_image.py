"""Host self time of the program's ``nc.pool`` spans (the bit-serial max
and average pools on the host, and their copies), per image
(``program_spans.py``); nothing where the program has no such span."""

from chipbench.program_spans import stage_ms_per_image


def read(run):
    return stage_ms_per_image(run, "pool")
