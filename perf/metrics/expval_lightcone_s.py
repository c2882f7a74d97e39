"""Host seconds of the program's phase ``tnc.expval.lightcone`` in
set-up: the backward walk of the 127-qubit gate list and the cone's
circuit. Set-up runs before the traced window, so the number comes from
the program's own phase totals (``obs.collect_phases`` round the bind),
not from the trace. Nothing from a program that has no such phase."""

name = 'expval_lightcone_s'
unit = 's'
layer = 'queries'
moves = 'setup_s'
workloads = ['kicked_ising127.expectation']


def read(run):
    return (run.setup.get('expval_phases') or {}).get('lightcone')
