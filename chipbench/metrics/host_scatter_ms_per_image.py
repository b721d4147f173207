"""Host self time of the program's ``nc.pallas.scatter`` and
``nc.conv.store`` spans (the adapter's scatter into the broadcast grid,
and each tile's values into the layer's output), per image
(``program_spans.py``); nothing where the program has no such span."""

from chipbench.program_spans import stage_ms_per_image


def read(run):
    return stage_ms_per_image(run, "scatter")
