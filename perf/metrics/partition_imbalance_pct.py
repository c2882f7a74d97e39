"""100 x (1 - mean / max) of the chips' device op seconds in their
partitions' local programs (``jit_tnc_partition_local``), from the traced
window chip by chip (``perf/chip_lib.py``): 0 when the partitioner gave
every chip the same work, 75 when one of four chips did it all. Nothing
without a trace, or from a program that does not name its local programs."""

from perf import chip_lib

name = 'partition_imbalance_pct'
unit = '%'
layer = 'multi-chip'
moves = 'amplitude_s'
workloads = ['sycamore30_m14_part4.fanin_calls']


def read(run):
    return chip_lib.imbalance_pct(run.window.get("per_chip"))
