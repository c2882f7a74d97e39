"""Device seconds of the fan-in's pair contractions (the ops of
``jit_tnc_fanin_pair``, ``parallel/partitioned.py``) over all device op
seconds of the traced window, summed over the chips: what the serial
reduce costs beside the partitions' concurrent local phase. Nothing
without a trace, or from a program that does not name its pair programs."""

from perf import span_lib

name = 'fanin_device_share_pct'
unit = '%'
layer = 'multi-chip'
moves = 'amplitude_s'
workloads = ['sycamore30_m14_part4.fanin_calls']


def read(run):
    return span_lib.device_share_pct(run, "jit_tnc_fanin_pair")
