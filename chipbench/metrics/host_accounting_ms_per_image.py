"""Host self time of the program's ``nc.accounting`` spans (the
zero-operand and live-output counts, modeled cycles and the layer
reports, which serving never returns), per image (``program_spans.py``);
nothing where the program has no such span."""

from chipbench.program_spans import stage_ms_per_image


def read(run):
    return stage_ms_per_image(run, "accounting")
