"""Real operations the window's completed work needs (8 per complex
multiply-add, by the shapes of the plan's steps) over the window's seconds
and the peak of the chips used: the whole window's share of the chip's
peak, idle time and every overhead included."""

from perf import metric_lib

name = 'window_mfu.amp'
unit = '%'
layer = 'device'
moves = 'amplitude_s'
workloads = None  # every cell that reports `moves`


def read(run):
    return metric_lib.mfu_pct(run)
