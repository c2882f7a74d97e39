"""Device op seconds of the traced window that the program's op table
(``tnc_tpu.obs.device_op_table``) puts under exactly one step of the plan
or one named non-step scope (``tnc.slice.index``, ``tnc.slice.sum``,
``tnc.chunk.io``), over all device op seconds: the health of the per-step
instrument itself. Nothing without a trace, from a program that has no
such table, or from a stale one (``perf/step_lib.py``)."""

from perf import step_lib

name = 'step_attributed_pct.serve'
unit = '%'
layer = 'kernels'
moves = 'amps_per_s'
workloads = ['sycamore30_m14.xeb_closed64']


def read(run):
    return step_lib.attributed_pct(run)
