"""Device op seconds of the steps' relayouts, the parts ``prep`` (the
planned transposes and staged ops of the operands) and ``out`` (the
result's way to its stored or carried shape), over the attributed op
seconds of the traced window (``perf/step_lib.py``). Nothing without a
trace or without the program's op table."""

from perf import step_lib

name = 'step_prep_share_pct.amp'
unit = '%'
layer = 'kernels'
moves = 'amplitude_s'
workloads = ['sycamore53_m14.amp_slices', 'sycamore53_m14.amp_slices_spmd4', 'kicked_ising127.expectation', 'sycamore53_m14_batch64.batch_slices']


def read(run):
    return step_lib.part_share_pct(run, "prep", "out")
