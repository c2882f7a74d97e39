"""Sampling by correlated amplitude batches, and the samples' linear XEB.

How supremacy-class circuits are sampled with a tensor-network
simulator (qFlex, Villalonga et al., npj QI 5:86): leave a handful of
output qubits open, so ONE contraction yields the amplitudes of the 2^k
bitstrings that share the other bits; accept a bitstring of the batch by
frugal rejection sampling (Markov et al., arXiv:1807.10749); score the
accepted bitstrings by linear XEB, 2^n mean(p) - 1 (about 1 for samples
of the circuit itself, 0 for uniform bitstrings).

Run:  python examples/amplitude_batch_sampling.py
"""

import sys
from pathlib import Path

try:
    import tnc_tpu  # noqa: F401
except ModuleNotFoundError:  # running from a source checkout
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np

from tnc_tpu.builders.sycamore_circuit import sycamore_circuit
from tnc_tpu.queries import bind_amplitude_batch, linear_xeb, sample_from_batches


def main() -> None:
    qubits, cycles, open_qubits = 16, 10, [10, 11, 12, 13, 14, 15]
    circuit = sycamore_circuit(qubits, cycles, np.random.default_rng(7))
    # one plan and one program for every batch: only the closed bras change
    program = bind_amplitude_batch(circuit, open_qubits)

    closed = "0110100101"
    amps = program.amplitudes(closed)  # (2,)*6: axis j is open_qubits[j]
    bits = program.bitstrings(closed)
    best = int(np.argmax(np.abs(amps)))
    print(f"batch of {amps.size} amplitudes; the largest, at {bits[best]}: "
          f"{amps.reshape(-1)[best]:.5f}")

    samples, probs = sample_from_batches(program, 20, seed=0)
    for s, p in zip(samples[:5], probs):
        print(f"  {s}  p 2^n = {p * 2 ** qubits:.3f}")
    print(f"linear XEB of {len(samples)} samples: {linear_xeb(probs, qubits):.3f}")
    uniform = np.full(20, 2.0 ** -qubits)
    print(f"linear XEB of uniform bitstrings:  {linear_xeb(uniform, qubits):.3f}")


if __name__ == "__main__":
    main()
