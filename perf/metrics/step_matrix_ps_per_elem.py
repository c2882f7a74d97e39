"""Picoseconds a streamed element over the large (an operand of 2^18
elements or more), once-a-row steps whose streamed operand's prep
stays in the matrix form XLA re-tiles (gauss steps among them):
their device op seconds in the traced window over (slices a chip completed
x elements they stream a slice: operand in + result out). One 8-byte read
and one write at the v5e's 819 GB/s are 19.5 ps. Nothing without a trace,
without the program's op table, or where the plan has no such step."""

from perf import step_lib

name = 'step_matrix_ps_per_elem'
unit = 'ps/elem'
layer = 'kernels'
moves = 'amplitude_s'
workloads = ['sycamore53_m14.amp_slices', 'sycamore53_m14.amp_slices_spmd4', 'kicked_ising127.expectation', 'sycamore53_m14_batch64.batch_slices']


def read(run):
    return step_lib.form_ps_per_elem(run, "matrix")
