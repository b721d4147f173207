"""Host self time of the program's ``nc.conv.epilogue`` and ``nc.concat``
spans (zero-point correction, the result's copies, bias, ReLU, min/max
tree and requantization of each layer; requantization at each concat),
per image (``program_spans.py``); nothing where the program has no such
span."""

from chipbench.program_spans import stage_ms_per_image


def read(run):
    return stage_ms_per_image(run, "requant")
