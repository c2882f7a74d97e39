"""Device op seconds of the steps' ``dot`` part (the one real dot of a
block step, gauss's three dots and their sums: the sub-scope ``dot`` of
``tnc.step.*``, and every fusion that holds such a dot; the one-hot matmul
of a staged prep's lane permutation is ``prep``) over the attributed op
seconds of the traced window (``perf/step_lib.py``). Nothing without a trace
or without the program's op table."""

from perf import step_lib

name = 'step_dot_share_pct.serve'
unit = '%'
layer = 'kernels'
moves = 'amps_per_s'
workloads = ['sycamore30_m14.xeb_closed64']


def read(run):
    return step_lib.part_share_pct(run, "dot")
