"""The window's useful model FLOPs over the window's length times the
chip's bf16 peak: the whole step's share of the peak
(``benchlib.work.window_flops``; padded slots do not count)."""
from benchlib.work import window_flops


def read(run):
    return 100.0 * window_flops(run) / (run.window_s
                                        * run.peaks["bf16_flops"])
