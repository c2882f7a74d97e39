"""Share of the traced window in which the idlest chip was idle while a
call built its partitions' programs and placed their leaves (the
program's span ``tnc.partitioned.scatter``, ``parallel/partitioned.py``):
per-call host work, the analogue of ``spmd_idle_build_pct``. Nothing
without a trace, or from a program that writes no such span."""

from perf import span_lib

name = 'partition_scatter_wait_pct'
unit = '%'
layer = 'multi-chip'
moves = 'amplitude_s'
workloads = ['sycamore30_m14_part4.fanin_calls']


def read(run):
    return span_lib.idle_pct(run, 'partitioned.scatter')
