"""1 - (union of the intervals in which an op ran on the device) / window,
from the profiler trace of the traced window, per device; the idlest
device is reported."""

from perf import metric_lib

name = 'device_idle_pct.serve'
unit = '%'
layer = 'device'
moves = 'amps_per_s'
workloads = None  # every cell that reports `moves`


def read(run):
    return metric_lib.idle_pct(run)
