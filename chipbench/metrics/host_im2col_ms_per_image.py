"""Host self time of the program's ``nc.conv.im2col`` spans
(``nc_conv2d``'s prologue: quantize, pad, window extraction, lane casts,
occupancy validation), per image (``program_spans.py``); nothing where
the program has no such span."""

from chipbench.program_spans import stage_ms_per_image


def read(run):
    return stage_ms_per_image(run, "im2col")
