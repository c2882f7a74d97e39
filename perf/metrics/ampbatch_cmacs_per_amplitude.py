"""Complex multiply-adds of the plan that ran, all slices (the program's
``sliced_flops``), over the ``2^k`` amplitudes a batch yields: what one
amplitude of a correlated batch costs. A count, not a time. The sources
say a batch costs little more than one amplitude, so this should stand
near a single amplitude's plan over ``2^k`` (``sycamore53_m14``: 5.1e14 /
64 = 8e12); how far above it stands is what a planner PR is judged on.
Nothing from a program whose set-up counted no open qubits."""

name = 'ampbatch_cmacs_per_amplitude'
unit = 'cMAC'
layer = 'planner'
moves = 'amplitude_s'
workloads = ['sycamore53_m14_batch64.batch_slices']


def read(run):
    opened = (run.setup.get('ampbatch_phases') or {}).get('bind.open')
    cmacs = run.setup.get('sliced_cmacs')
    if not opened or cmacs is None:
        return None
    return cmacs / 2 ** int(opened)
