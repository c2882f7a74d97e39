"""Device op seconds of the steps' relayouts, the parts ``prep`` (the
planned transposes and staged ops of the operands) and ``out`` (the
result's way to its stored or carried shape), over the attributed op
seconds of the traced window (``perf/step_lib.py``). Nothing without a
trace or without the program's op table."""

from perf import step_lib

name = 'step_prep_share_pct.serve'
unit = '%'
layer = 'kernels'
moves = 'amps_per_s'
workloads = ['sycamore30_m14.xeb_closed64']


def read(run):
    return step_lib.part_share_pct(run, "prep", "out")
