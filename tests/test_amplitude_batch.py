"""Correlated amplitude batches (``tnc_tpu.queries.amplitude_batch``):
``2^k`` amplitudes from ``k`` open qubits in one contraction, each at its
own bitstring, pinned against the dense statevector (complex128, no
kernels, no slicing); frugal rejection sampling and linear XEB on top."""

from __future__ import annotations

import functools
import math

import numpy as np
import pytest

from tnc_tpu import obs
from tnc_tpu.builders.circuit_builder import Circuit
from tnc_tpu.builders.sycamore_circuit import sycamore_circuit
from tnc_tpu.ops.backends import JaxBackend, NumpyBackend
from tnc_tpu.queries import statevector as sv
from tnc_tpu.queries.amplitude_batch import (
    AmplitudeBatchProgram,
    bind_amplitude_batch,
    frugal_rejection_sample,
    linear_xeb,
    sample_from_batches,
)
from tnc_tpu.tensornetwork.tensordata import TensorData

# float32 products: a step loses 6e-8 of its operands' scale and a sum of
# some hundred slices of either sign stays under 1e-6 of the batch's
# largest amplitude (read: 2e-7 to 9e-7); 1e-5 leaves room for a longer
# sum and none for a wrong axis, which reads of order 1.
TOL = 1e-5


def _non_lattice(n: int, gates: int, seed: int) -> Circuit:
    """Random pairs of any two qubits: no lattice, mixed gate set."""
    rng = np.random.default_rng(seed)
    c = Circuit()
    reg = c.allocate_register(n)
    for q in range(n):
        c.append_gate(TensorData.gate("h"), [reg.qubit(q)])
    for _ in range(gates):
        a, b = (int(q) for q in rng.choice(n, size=2, replace=False))
        c.append_gate(TensorData.gate("rx", (float(rng.uniform(0, 3)),)), [reg.qubit(a)])
        c.append_gate(TensorData.gate("ry", (float(rng.uniform(0, 3)),)), [reg.qubit(b)])
        two = (
            TensorData.gate("cz") if rng.random() < 0.5
            else TensorData.gate("fsim", (math.pi / 2, math.pi / 6))
        )
        c.append_gate(two, [reg.qubit(a), reg.qubit(b)])
    return c


CIRCUITS = {
    "sycamore12": lambda: sycamore_circuit(12, 8, np.random.default_rng(12)),
    "sycamore16": lambda: sycamore_circuit(16, 8, np.random.default_rng(16)),
    "sycamore20": lambda: sycamore_circuit(20, 8, np.random.default_rng(20)),
    "nonlattice14": lambda: _non_lattice(14, 40, 14),
}
OPEN = {  # by circuit: k in {1, 3, 6}, contiguous and scattered, any order
    "sycamore12": [(4,), (9, 2, 6), (6, 7, 8, 9, 10, 11)],
    "sycamore16": [(15,), (5, 6, 7), (14, 0, 9, 3, 12, 6)],
    "sycamore20": [(17, 3, 11), (2, 3, 4, 5, 6, 7)],
    "nonlattice14": [(0,), (13, 1, 7), (12, 2, 5, 9, 0, 7)],
}
CASES = [(name, opened) for name in CIRCUITS for opened in OPEN[name]]
BACKENDS = {
    "numpy": lambda: NumpyBackend(),
    "jax": lambda: JaxBackend(),
    "jax-split": lambda: JaxBackend(split_complex=True),
}


@functools.lru_cache(maxsize=None)
def _state(name: str) -> np.ndarray:
    return sv.statevector(CIRCUITS[name]())


@functools.lru_cache(maxsize=None)
def _program(name: str, opened: tuple, sliced: bool) -> AmplitudeBatchProgram:
    if not sliced:
        return bind_amplitude_batch(CIRCUITS[name](), opened)
    for log2 in range(14, len(opened), -1):  # the first budget that forces 64 slices
        prog = bind_amplitude_batch(CIRCUITS[name](), opened, target_size=2.0 ** log2)
        if prog.num_slices >= 64:
            return prog
    raise AssertionError(f"no budget slices {name} {opened} 64 times")


def _closed_bits(prog, seed: int) -> str:
    rng = np.random.default_rng(seed)
    return "".join("01"[b] for b in rng.integers(0, 2, size=len(prog.closed_qubits)))


def _want(state: np.ndarray, prog, closed_bits: str) -> np.ndarray:
    """The dense statevector at the batch's bitstrings, axis ``j`` =
    ``open_qubits[j]``."""
    index: list = [slice(None)] * prog.num_qubits
    for q, c in zip(prog.closed_qubits, closed_bits):
        index[q] = int(c)
    ascending = sorted(prog.open_qubits)
    return np.transpose(state[tuple(index)], [ascending.index(q) for q in prog.open_qubits])


@pytest.mark.parametrize("sliced", [False, True], ids=["unsliced", "sliced"])
@pytest.mark.parametrize("backend", list(BACKENDS))
@pytest.mark.parametrize("name,opened", CASES, ids=[f"{n}-{'.'.join(map(str, o))}" for n, o in CASES])
def test_every_amplitude_of_the_batch_is_the_statevectors(name, opened, backend, sliced):
    prog = _program(name, opened, sliced)
    assert (prog.num_slices >= 64) == sliced
    closed = _closed_bits(prog, 5)
    want = _want(_state(name), prog, closed)
    got = prog.amplitudes(closed, BACKENDS[backend]())
    assert got.shape == (2,) * len(opened)
    scale = np.abs(want).max()
    assert scale > 0 and np.abs(got - want).max() < TOL * scale
    # each sits at its own bitstring
    flat = got.reshape(-1)
    for i, bits in list(enumerate(prog.bitstrings(closed)))[:: max(1, flat.size // 8)]:
        assert abs(flat[i] - sv.amplitude(_state(name), bits)) < TOL * scale


def _tagged_circuit(n: int):
    """Every qubit carries rotations of its own (``ry``, ``rx``, ``rz`` by
    angles that grow with the qubit's number) between three brick layers
    of ``cz``: no two axes of a batch can be taken for one another."""
    c = Circuit()
    reg = c.allocate_register(n)
    for layer, name in enumerate(("ry", "rx", "ry")):
        for q in range(n):
            c.append_gate(TensorData.gate(name, (0.3 + 0.2 * q + 0.5 * layer,)), [reg.qubit(q)])
        for start in (0, 1):
            for q in range(start, n - 1, 2):
                c.append_gate(TensorData.gate("cz"), [reg.qubit(q), reg.qubit(q + 1)])
    for q in range(n):
        c.append_gate(TensorData.gate("rz", (0.1 + 0.4 * q,)), [reg.qubit(q)])
    return c


@pytest.mark.parametrize("opened", [(1, 4, 6, 8), (8, 1, 6, 4), (9, 0), (3, 2, 5, 7, 4)])
@pytest.mark.parametrize("sliced", [False, True], ids=["unsliced", "sliced"])
def test_axis_order_is_the_open_qubits_order(opened, sliced):
    """A permuted result must fail: every transposition of the axes moves
    the answer by far more than the tolerance."""
    import itertools

    state = sv.statevector(_tagged_circuit(10))
    target = 2.0 ** max(len(opened) + 1, 5) if sliced else None
    prog = bind_amplitude_batch(_tagged_circuit(10), opened, target_size=target)
    assert (prog.num_slices > 1) == sliced
    closed = _closed_bits(prog, 3)
    want = _want(state, prog, closed)
    got = prog.amplitudes(closed)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() < 1e-12 * scale
    k = len(opened)
    for perm in itertools.permutations(range(k)):
        if perm != tuple(range(k)):
            assert np.abs(np.transpose(got, perm) - want).max() > 1e-2 * scale, perm
    # the executor's own order is some permutation of the qubits: the
    # permutation applied inside is what puts it right
    raw = prog.bound.amplitudes_det([closed])[0]
    np.testing.assert_allclose(np.transpose(raw, prog.permutation), got, atol=1e-15)


@pytest.mark.parametrize("backend", list(BACKENDS))
def test_slice_range_halves_add_up_and_host_false_is_host_true(backend):
    prog = _program("sycamore16", (14, 0, 9, 3, 12, 6), True)
    be = BACKENDS[backend]()
    closed = _closed_bits(prog, 8)
    whole = prog.amplitudes(closed, be)
    n, half = prog.num_slices, prog.num_slices // 2
    parts = [prog.amplitudes(closed, be, slice_range=r) for r in ((0, half), (half, n))]
    scale = np.abs(whole).max()
    assert np.abs(parts[0] + parts[1] - whole).max() < 1e-6 * scale
    assert np.abs(parts[0]).max() > 1e-3 * scale  # a half is not nothing
    # host=False: the executor's value, in flight; the permutation after
    on_device = prog.amplitudes(closed, be, slice_range=(0, half), host=False)
    planes = on_device if isinstance(on_device, tuple) else (on_device,)
    assert all(np.shape(p) == tuple(prog.bound.sliced.program.stored_result_shape) for p in planes)
    assert np.array_equal(prog.to_host(on_device), parts[0])
    fetched = (
        np.asarray(planes[0]) + 1j * np.asarray(planes[1]) if len(planes) == 2
        else np.asarray(planes[0])
    )
    by_hand = np.transpose(fetched.reshape((2,) * 6), prog.permutation)
    assert np.array_equal(by_hand, parts[0])


def test_host_false_and_slice_range_need_a_sliced_structure():
    prog = _program("sycamore12", (4,), False)
    closed = _closed_bits(prog, 1)
    with pytest.raises(ValueError, match="sliced structures"):
        prog.amplitudes(closed, slice_range=(0, 1))
    with pytest.raises(ValueError, match="sliced structures"):
        prog.amplitudes(closed, host=False)


def test_slice_hooks_pass_to_a_backend_that_has_them():
    prog = _program("sycamore12", (9, 2, 6), True)
    closed = _closed_bits(prog, 2)
    seen = []
    got = prog.amplitudes(closed, NumpyBackend(), on_slice=lambda s: seen.append(s))
    assert seen == list(range(1, prog.num_slices))  # the cursor after each slice but the last
    np.testing.assert_allclose(got, prog.amplitudes(closed), atol=1e-15)
    # dropped where the backend has none: the answer is the same
    np.testing.assert_allclose(
        prog.amplitudes(closed, JaxBackend(), on_slice=lambda s: seen.append(s)),
        got, atol=1e-6 * np.abs(got).max(),
    )


def test_a_template_over_the_core_plan_threshold_with_open_legs():
    """More than ``CORE_PLAN_MIN_LEAVES`` raw leaves under a budget: the
    plan is searched on the rank>=3 cores, open legs and all, lifted
    back, and no open leg is sliced."""
    from tnc_tpu.serve import rebind

    opened = (17, 2, 9, 18)
    circuit = sycamore_circuit(20, 10, np.random.default_rng(7))
    state = sv.statevector(circuit)
    prog = bind_amplitude_batch(circuit, opened, target_size=2.0 ** 9)
    network = prog.bound.template.network
    assert len(network.tensors) > rebind.CORE_PLAN_MIN_LEAVES
    assert len(network.tensors) >= 300
    prefix, core_ids, _, cores = rebind._budget_cores(network)
    assert min(len(t.legs) for t in cores.tensors) >= 3
    sliced = prog.bound.sliced
    assert sliced is not None and sliced.slicing.num_slices >= 64
    open_legs = set(prog.bound.template.permutor.target_leg_order)
    assert not open_legs & set(sliced.slicing.legs)
    assert set(prog.bound.result_legs) == open_legs
    closed = _closed_bits(prog, 4)
    want = _want(state, prog, closed)
    for backend in (NumpyBackend(), JaxBackend(split_complex=True)):
        got = prog.amplitudes(closed, backend)
        assert np.abs(got - want).max() < TOL * np.abs(want).max()


def test_errors():
    def circuit():
        return sycamore_circuit(6, 2, np.random.default_rng(0))

    with pytest.raises(ValueError, match="open qubit 6"):
        bind_amplitude_batch(circuit(), [1, 6])
    with pytest.raises(ValueError, match="open qubit -1"):
        bind_amplitude_batch(circuit(), [-1])
    with pytest.raises(ValueError, match="repeat"):
        bind_amplitude_batch(circuit(), [2, 4, 2])
    with pytest.raises(ValueError, match="at least one"):
        bind_amplitude_batch(circuit(), [])
    prog = bind_amplitude_batch(circuit(), [2, 4])
    for bad in ("010", "01010", "01*1", "01x1"):
        with pytest.raises(ValueError):
            prog.amplitudes(bad)
    with pytest.raises(ValueError, match="closed_bits"):
        prog.bitstrings("0" * 6)
    assert prog.closed_qubits == (0, 1, 3, 5) and prog.open_qubits == (2, 4)
    assert prog.bitstrings("0110") == ["010100", "010110", "011100", "011110"]
    assert prog.bitstrings([0, 1, 1, 0]) == prog.bitstrings("0110")


def test_phases_and_counters(registry):
    prog = None
    with obs.collect_phases() as totals:
        prog = bind_amplitude_batch(
            sycamore_circuit(12, 8, np.random.default_rng(12)), (9, 2, 6), target_size=256.0
        )
        closed = _closed_bits(prog, 2)
        prog.amplitudes(closed)
        prog.amplitudes(closed, slice_range=(0, 4))
    assert totals["ampbatch.bind"] > 0 and totals["ampbatch.amplitudes"] > 0
    assert totals["ampbatch.bind.leaves"] == len(prog.bound.arrays)
    assert totals["ampbatch.amplitudes.slices"] == prog.num_slices + 4
    assert totals["ampbatch.rebind.leaves"] == 2 * 9
    assert totals["ampbatch.rebind.bytes"] == 2 * 9 * 32
    flat = {k[0]: v for k, v in registry.counters().items()}
    assert flat["ampbatch.sliced_calls"] == 2
    assert flat["ampbatch.amplitudes"] == 8  # one whole batch; a range is not one
    assert {k[0]: v for k, v in registry.gauges().items()}["ampbatch.open_qubits"] == 3
    spans = {r.name: r for r in registry.span_records()}
    assert totals["ampbatch.bind.open"] == 3
    assert spans["ampbatch.amplitudes"].args["open"] == 3


# -- the sampler ---------------------------------------------------------------


def test_frugal_rejection_exact_proportions_under_the_ceiling():
    """All candidates under the ceiling, a batch of ONE tried candidate a
    time: accepted indices follow p exactly."""
    n = 3
    p = np.array([0.30, 0.05, 0.15, 0.10, 0.02, 0.18, 0.12, 0.08])  # 8 p <= 2.4 < 10
    rng = np.random.default_rng(1)
    counts = np.zeros(8)
    tried = accepted = 0
    while accepted < 20000:
        i = int(rng.integers(0, 8))
        hit = frugal_rejection_sample(p[i: i + 1], n, rng, ceiling=10.0)
        tried += 1
        if hit is not None:
            assert hit == 0
            counts[i] += 1
            accepted += 1
    chi2 = float(np.sum((counts - 20000 * p) ** 2 / (20000 * p)))
    assert chi2 < 24.3  # 7 degrees of freedom, p = 0.001
    assert abs(accepted / tried - 1 / 10.0) < 0.005  # mean acceptance 1/ceiling


def test_frugal_rejection_clips_over_the_ceiling():
    n = 2
    p = np.array([0.85, 0.05, 0.05, 0.05])  # 4 * 0.85 = 3.4 over a ceiling of 2
    rng = np.random.default_rng(2)
    accept = np.minimum(1.0, p * 4 / 2.0)
    assert accept[0] == 1.0 and accept[1] == pytest.approx(0.1)
    counts = np.zeros(4)
    for _ in range(20000):
        i = int(rng.integers(0, 4))
        if frugal_rejection_sample(p[i: i + 1], n, rng, ceiling=2.0) is not None:
            counts[i] += 1
    law = accept / accept.sum()  # 0.769 for the clipped one, not 0.85
    total = counts.sum()
    assert float(np.sum((counts - total * law) ** 2 / (total * law))) < 16.3  # 3 dof
    assert abs(counts[0] / total - law[0]) < 0.015 < abs(law[0] - p[0])
    # a batch: the first accepted in the drawn order, or nothing
    assert frugal_rejection_sample(np.zeros(8), 3, rng) is None
    assert frugal_rejection_sample([0.0, 0.0, 1.0, 0.0], 2, rng) == 2
    firsts = {frugal_rejection_sample(np.full(8, 1.0), 3, np.random.default_rng(s)) for s in range(40)}
    assert len(firsts) > 4  # the order is drawn, not 0, 1, 2, ...


def test_linear_xeb():
    assert linear_xeb(np.full(10, 2.0 ** -5), 5) == pytest.approx(0.0)
    assert linear_xeb([2.0 ** -4, 3 * 2.0 ** -4], 5) == pytest.approx(3.0)


def _first_accepted_law(accept: np.ndarray) -> np.ndarray:
    """P(candidate i is the first accepted) when a batch's candidates are
    tried in a uniformly drawn order: ``a_i / K * sum_m e_m(1 - a_others)
    / C(K - 1, m)`` (i stands at a uniform position; the m before it are a
    uniform m-subset of the others, none of them accepted)."""
    k = accept.size
    binom = np.array([math.comb(k - 1, m) for m in range(k)], dtype=float)
    out = np.zeros(k)
    for i in range(k):
        e = np.zeros(k)
        e[0] = 1.0
        for x in np.delete(1.0 - accept, i):
            e[1:] = e[1:] + x * e[:-1]
        out[i] = accept[i] * np.sum(e / binom) / k
    return out


def test_samples_from_batches_follow_the_rules_law():
    """4000 samples at 12 qubits, six open. The chi-square is against the
    EXACT law of the rule as the sources state it (uniform prefix, the
    first accepted of the batch in a drawn order), computed from
    ``|psi|^2`` in closed form. That law is ``|psi|^2`` up to the
    batches' share of the mass: one sample a batch whatever the batch
    weighs flattens the closed bits' marginal (total variation 5 to 7 %
    at this size and depth), which 4000 samples resolve."""
    n, opened, n_samples = 12, (6, 7, 8, 9, 10, 11), 4000
    circuit = sycamore_circuit(n, 14, np.random.default_rng(3))
    p = (np.abs(sv.statevector(circuit)) ** 2).reshape(64, 64)  # [closed, open]
    prog = bind_amplitude_batch(circuit, opened)
    contract = prog.amplitudes
    batches = []

    @functools.lru_cache(maxsize=None)  # 64 prefixes: contract each once
    def cached(bits):
        return contract(bits, NumpyBackend())

    def counted(bits, backend=None):
        batches.append(bits)
        return cached(bits)

    prog.amplitudes = counted
    samples, probs = sample_from_batches(prog, n_samples, NumpyBackend(), seed=11)
    n_batches = len(batches)
    assert len(samples) == n_samples == len(probs) <= n_batches
    again, _ = sample_from_batches(prog, 50, NumpyBackend(), seed=11)
    assert again == samples[:50]  # deterministic in the seed
    assert sample_from_batches(prog, 50, NumpyBackend(), seed=12)[0] != again
    flat = p.reshape(-1)
    np.testing.assert_allclose(probs, flat[[int(s, 2) for s in samples]], rtol=1e-9)

    accept = np.minimum(1.0, p * 2.0 ** n / 10.0)
    law = np.stack([_first_accepted_law(row) for row in accept]) / 64
    mean_acceptance = law.sum()
    assert mean_acceptance == pytest.approx(np.mean(1 - np.prod(1 - accept, axis=1)))
    law = (law / mean_acceptance).reshape(-1)
    assert 0.02 < 0.5 * np.abs(law - flat).sum() < 0.10  # near |psi|^2, not it

    # mean acceptance a batch against its closed form (binomial error)
    sigma = math.sqrt(mean_acceptance * (1 - mean_acceptance) / n_batches)
    assert abs(n_samples / n_batches - mean_acceptance) < 4 * sigma + 1e-3

    # chi-square of the frequencies, cells of expected count >= 5
    counts = np.bincount([int(s, 2) for s in samples], minlength=flat.size)
    order = np.argsort(law)
    cells_e, cells_o, e, o = [], [], 0.0, 0
    for i in order:
        e += n_samples * law[i]
        o += counts[i]
        if e >= 5.0:
            cells_e.append(e), cells_o.append(o)
            e, o = 0.0, 0
    cells_e[-1] += e
    cells_o[-1] += o
    cells_e, cells_o = np.array(cells_e), np.array(cells_o)
    dof = len(cells_e) - 1
    chi2 = float(np.sum((cells_o - cells_e) ** 2 / cells_e))
    assert dof > 300 and abs(chi2 - dof) < 4 * math.sqrt(2 * dof), (chi2, dof)

    # linear XEB of the samples: 2^n sum(law p) - 1, which is
    # 2^n sum(p^2) - 1 to within the standard error at this count
    expected = 2.0 ** n * float(np.sum(law * flat)) - 1.0
    ideal = 2.0 ** n * float(np.sum(flat ** 2)) - 1.0
    se = 2.0 ** n * math.sqrt(float(np.sum(law * flat ** 2) - np.sum(law * flat) ** 2) / n_samples)
    xeb = linear_xeb(probs, n)
    assert abs(xeb - expected) < 3 * se and abs(xeb - ideal) < 3 * se
    assert 0.5 < ideal < 1.5 and abs(expected - ideal) < 2 * se
