"""The largest picoseconds a streamed element of any ONE large once-a-row
``block`` step over the median of them: the step that lowered badly (a fused
step can: PERF.md, PR 36). Nothing without a trace, without the program's
op table, or with fewer than two such steps (``perf/step_lib.py``)."""

from perf import step_lib

name = 'step_worst_ratio'
unit = 'ratio'
layer = 'kernels'
moves = 'amplitude_s'
workloads = ['sycamore53_m14.amp_slices', 'sycamore53_m14.amp_slices_spmd4', 'kicked_ising127.expectation', 'sycamore53_m14_batch64.batch_slices']


def read(run):
    return step_lib.worst_ratio(run)
