"""Host self time of the program's ``nc.conv.pack`` spans (the per-layer
filter pack and each miss of ``nc_conv2d``'s per-tile window and filter
caches, the CSR round trip included), per image (``program_spans.py``);
nothing where the program has no such span."""

from chipbench.program_spans import stage_ms_per_image


def read(run):
    return stage_ms_per_image(run, "pack")
