"""Least time the chip could take for the contraction steps of the window
(``perf/roofline.py``, from the shapes of the plan's steps) over the summed
device time of the ops in the traced window (``perf/trace_reduce.py``), on
the busiest device. A reader with no trace says nothing."""

from perf import metric_lib

name = 'contraction_roofline.serve'
unit = '%'
layer = 'kernels'
moves = 'amps_per_s'
workloads = None  # every cell that reports `moves`


def read(run):
    return metric_lib.roofline_pct(run)
