"""Device op seconds of the traced window that the program's op table
(``tnc_tpu.obs.device_op_table``) puts under exactly one step of the plan
or one named non-step scope (``tnc.slice.index``, ``tnc.slice.sum``,
``tnc.chunk.io``), over all device op seconds: the health of the per-step
instrument itself. Nothing without a trace, from a program that has no
such table, or from a stale one (``perf/step_lib.py``)."""

from perf import step_lib

name = 'step_attributed_pct.amp'
unit = '%'
layer = 'kernels'
moves = 'amplitude_s'
workloads = ['sycamore53_m14.amp_slices', 'sycamore53_m14.amp_slices_spmd4', 'kicked_ising127.expectation', 'sycamore53_m14_batch64.batch_slices']


def read(run):
    return step_lib.attributed_pct(run)
