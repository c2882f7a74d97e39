"""Complex multiply-adds of the plan that ran, all slices, as the program's
``sliced_flops`` counts them. A count, not a time."""

name = 'plan_sliced_cmacs'
unit = 'cMAC'
layer = 'planner'
moves = 'amplitude_s'
workloads = None  # every cell that reports `moves`


def read(run):
    return run.setup.get('sliced_cmacs')
