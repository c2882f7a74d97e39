"""tnc_tpu.queries — the query engine: bitstring sampling, Pauli
expectation values and marginal sweeps as first-class query types.

Everything the stack serves is a contraction of one circuit's tensor
networks; this package adds the queries a real user fleet asks
for beyond single amplitudes, all riding the existing planning,
rebinding, batching and serving machinery:

- **Sampling** (``sampling.py``) — qubit-by-qubit chain-rule sampling
  over marginal sandwich networks: one planned structure per prefix
  length (plan-cache keyed), conditionals rebound and batched across
  all in-flight samples, seeded-deterministic streams.
- **Expectation values** (``expectation.py``) — ⟨ψ|P|ψ⟩ sandwich
  networks with rebindable observable leaves; Pauli-sum terms batch
  like bras through one compiled program; ``value_and_grad`` through
  the autodiff-capable jax executors.
- **Correlated amplitude batches** (``amplitude_batch.py``) — ``k``
  output qubits left OPEN, so one contraction yields the ``2^k``
  amplitudes of the bitstrings that share the other bits, each at its
  own bitstring whatever order the plan leaves the axes in; frugal
  rejection sampling and linear XEB on top. A batch at 53 qubits is
  hours of slices (``slice_range=``); the service gets no handler.
- **Marginal sweeps** (``marginal.py``) — wildcard patterns contract
  as traced sandwich legs, returning marginal probabilities of the
  determined positions (this is ``amplitude_sweep``'s lifted ``'*'``
  case).
- **Dense oracle** (``statevector.py``) — brute-force ``O(2^n)``
  ground truth for all of the above, used by the exactness pins.
- **Service handlers** (``handlers.py``) — the three types as
  ``submit()``-able requests on a
  :class:`~tnc_tpu.serve.service.ContractionService` mixed queue with
  per-type batching keys.

See ``docs/serving.md`` ("Query types").
"""

from tnc_tpu.queries.amplitude_batch import (  # noqa: F401
    AmplitudeBatchProgram,
    bind_amplitude_batch,
    frugal_rejection_sample,
    linear_xeb,
    sample_from_batches,
)
from tnc_tpu.queries.expectation import (  # noqa: F401
    ExpectationProgram,
    bind_expectation,
    pauli_expectation,
    pauli_expectation_value_and_grad,
    pauli_sum_expectation,
)
from tnc_tpu.queries.handlers import (  # noqa: F401
    ExpectationQueryHandler,
    MarginalQueryHandler,
    SampleQueryHandler,
    attach_query_handlers,
)
from tnc_tpu.queries.marginal import (  # noqa: F401
    bind_marginal,
    marginal_sweep,
    wildcard_mask,
)
from tnc_tpu.queries.sampling import (  # noqa: F401
    ChainSampler,
    sample_bitstrings,
)

# NOTE: the dense-oracle helpers live in ``tnc_tpu.queries.statevector``
# (not re-exported here: the module shares its name with its main
# function, and the module is the stable import path).
