"""Share of the traced window in which the chip was idle while the host
was inside ``ExpectationProgram.values`` (program span
``tnc.expval.values``, the parent of a call): observables and parameters
rebound, 1230 leaves looked up in the resident-leaf store, the chunk
programs of the first batch dispatched. What the queries layer puts in
front of a call of the sliced executor; the reduction shares a gap out to
every span that overlaps it, so this holds what ``tnc.backend.lookup``,
``tnc.backend.place_buffers`` and ``tnc.sliced.*`` read under it. (The
hoisted prelude is not run inside a window since the last prelude's
outputs are kept: a reader of ``jit_tnc_prelude``'s device seconds, which
the issue asked for, would find nothing to read here.) Nothing without a
trace, or from a program that writes no such span."""

from perf import span_lib

name = 'expval_values_wait_pct'
unit = '%'
layer = 'queries'
moves = 'amplitude_s'
workloads = ['kicked_ising127.expectation']


def read(run):
    return span_lib.idle_pct(run, "expval.values")
