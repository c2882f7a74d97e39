"""Share of the traced window in which the chip was idle while the host
was inside ``AmplitudeBatchProgram.amplitudes`` (program span
``tnc.ampbatch.amplitudes``, the parent of a call): the closed bras
rebound, 1196 leaves looked up in the resident-leaf store, the chunk
programs of the first batch dispatched. What the queries layer puts in
front of a call of the sliced executor; the reduction shares a gap out to
every span that overlaps it, so this holds what ``tnc.backend.lookup``,
``tnc.backend.place_buffers`` and ``tnc.sliced.*`` read under it. Nothing
without a trace, or from a program that writes no such span."""

from perf import span_lib

name = 'ampbatch_wait_pct'
unit = '%'
layer = 'queries'
moves = 'amplitude_s'
workloads = ['sycamore53_m14_batch64.batch_slices']


def read(run):
    return span_lib.idle_pct(run, "ampbatch.amplitudes")
