"""Host clock around planning in set-up: ``find_path`` and
``slice_and_reconfigure`` (served cells: plan + bind of ``from_circuit``)."""

name = 'plan_s'
unit = 's'
layer = 'planner'
moves = 'setup_s'
workloads = None  # every cell that reports `moves`


def read(run):
    return run.setup.get('plan_s')
