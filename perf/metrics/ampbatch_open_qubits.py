"""Qubits the batch leaves open, as the program's phase
``tnc.ampbatch.bind`` counts them (``open``; the gauge
``ampbatch.open_qubits`` holds the same): a count, 6 for the
configuration, 64 amplitudes a contraction. Nothing from a program that
counts no such thing."""

name = 'ampbatch_open_qubits'
unit = 'qubits'
layer = 'queries'
moves = 'amplitude_s'
workloads = ['sycamore53_m14_batch64.batch_slices']


def read(run):
    return (run.setup.get('ampbatch_phases') or {}).get('bind.open')
