"""Host seconds of the program's phase ``tnc.plan.store`` in set-up: the
look-up of the network's structure digest in the read-only plan store,
reading the entry, and rebuilding the sliced program from its stored
pairs and slicing. Set-up runs before the traced window, so the number
comes from the program's own phase totals (``obs.collect_phases`` round
the load), not from the trace. Nothing from a program that has no such
phase."""

name = 'plan_store_load_s'
unit = 's'
layer = 'planner'
moves = 'setup_s'
workloads = ['sycamore53_m20_share.amp_slices']


def read(run):
    return (run.setup.get('plan_store') or {}).get('seconds')
