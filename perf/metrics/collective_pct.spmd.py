"""Summed duration of the collective ops (all-reduce and its kin) on the
device that spent most in them, over the traced window, from the profiler
trace. A reader with no trace, or a trace with no collective, says nothing."""

name = 'collective_pct.spmd'
unit = '%'
layer = 'multi-chip'
moves = 'amplitude_s'
workloads = ['sycamore53_m14.amp_slices_spmd4']


def read(run):
    if not run.reduced or not run.reduced["collective_s_max"]:
        return None
    return 100.0 * run.reduced["collective_s_max"] / run.reduced["window_s"]
