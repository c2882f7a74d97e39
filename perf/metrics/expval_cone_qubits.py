"""Qubits the operator's lightcone keeps, as the program's phase
``tnc.expval.lightcone`` counts them (``kept_qubits``): a count, 68 of
127 for the configuration's operator. The width of the sandwich that is
planned and sliced; a cone that grew would show here before it showed in
``amplitude_s``. Nothing from a program that counts no such thing."""

name = 'expval_cone_qubits'
unit = 'qubits'
layer = 'queries'
moves = 'amplitude_s'
workloads = ['kicked_ising127.expectation']


def read(run):
    return (run.setup.get('expval_phases') or {}).get('lightcone.kept_qubits')
