"""Stem fusion (``tnc_tpu.contractionpath.stem_fusion``): small operands
that meet a value of 2^18 elements or more one after another are
multiplied together first, ``((S·W1)·W2)·W3 -> S·(W1·W2·W3)``.

Held here, on the numpy oracle in complex128: the re-associated path
contracts to the value of the path it came from (closed and with open
legs, unsliced and slice by slice); the bounds of the rule (the joined
tensor contracts at most ``BLOCK_MAX_CONTRACT / 2`` with the stem and
adds at most as much, nothing under a 2^18 stem, a gauss step or a step
of two large values is left alone, legs two small operands share are
contracted between them); the small products of hoisted operands land in
the prelude; the search's ranking call (``fuse=False``) and the plan that
is handed out slice alike; the counter and
``kernel_plan_summary(...)["fusion"]`` say what the pass did.
"""

from __future__ import annotations


import numpy as np
import pytest

from tnc_tpu import obs
from tnc_tpu.contractionpath.contraction_path import (
    ContractionPath,
    replace_ssa_ordering,
    ssa_replace_ordering,
)
from tnc_tpu.contractionpath.slicing import Slicing, slice_and_reconfigure
from tnc_tpu.contractionpath.stem_fusion import fuse_stem_operands, stem_bounds
from tnc_tpu.ops.backends import NumpyBackend
from tnc_tpu.ops.hoist import hoist_sliced_program
from tnc_tpu.ops.program import build_program, step_dims
from tnc_tpu.ops.sliced import build_sliced_program, execute_sliced_numpy
from tnc_tpu.ops.split_complex import BLOCK_MAX_CONTRACT, kernel_plan_summary
from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor

STEM_MIN, JOINED_MAX = stem_bounds()


def _leaf(rng, legs):
    shape = (2,) * len(legs)
    data = rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
    return LeafTensor(list(legs), [2] * len(legs)), data / data.size ** 0.25


def _stem_network(seed: int, wires: int, gates: int, open_legs: int):
    """A stem of ``wires`` extent-2 legs and a chain of ``gates`` small
    tensors applied to it one after another, as a circuit is applied to
    a state: each takes one to three of the current wires in and gives
    zero to three out (so some share legs with the stem, some with the
    gate before them, and the stem grows and, in the end, shrinks), then
    one closing
    tensor per wire but ``open_legs``. Returns ``(leaves, arrays,
    ssa_pairs)`` with the chain as the path."""
    rng = np.random.default_rng(seed)
    leaves, arrays = [], []

    def add(legs):
        leaf, data = _leaf(rng, legs)
        leaves.append(leaf)
        arrays.append(data)

    current = list(range(wires))
    add(current)
    next_leg = wires
    for g in range(gates):
        n_in = int(rng.integers(1, 4))
        room = wires + 1 - (len(current) - n_in)
        # the first gates keep the stem at its size or one leg over it
        low = max(wires - (len(current) - n_in), 0) if g < gates - 4 else 0
        n_out = min(max(int(rng.integers(0, 4)), low), room)
        # recent wires first: a gate often meets the one before it
        taken = [current.pop(int(rng.integers(max(0, len(current) - 4), len(current))))
                 for _ in range(n_in)]
        made = list(range(next_leg, next_leg + n_out))
        next_leg += n_out
        current.extend(made)
        add(taken + made)
    for leg in current[open_legs:]:
        add([leg])
    n = len(leaves)
    pairs = [(0, 1)] + [(n + i - 1, i + 1) for i in range(1, n - 1)]
    return leaves, arrays, pairs


def _network(leaves, arrays):
    from tnc_tpu.tensornetwork.tensordata import TensorData

    tn = CompositeTensor()
    for leaf, data in zip(leaves, arrays):
        tn.push_tensor(LeafTensor(list(leaf.legs), list(leaf.bond_dims), TensorData.matrix(data)))
    return tn


def _replace(ssa_pairs):
    return ssa_replace_ordering(ContractionPath.simple(list(ssa_pairs)))


def _by_leg(program, value):
    """A program's result with its axes in ascending leg order."""
    value = np.asarray(value).reshape(program.result_shape)
    order = np.argsort(program.result_legs)
    return np.transpose(value, order) if len(order) else value


def _close(got, want):
    scale = max(float(np.abs(want).max()), 1e-300)
    return float(np.abs(got - want).max()) / scale


@pytest.mark.parametrize("sliced", [0, 2], ids=["unsliced", "sliced"])
@pytest.mark.parametrize("open_legs", [0, 3], ids=["closed", "open"])
@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_the_fused_path_contracts_to_the_same_value(seed, open_legs, sliced):
    """Random stem-shaped networks: the path the pass hands out gives
    the value of the path it came from, every slice and the sum."""
    wires = 18 + sliced
    leaves, arrays, pairs = _stem_network(seed, wires, 14, open_legs)
    # slice legs the stem shares with gates (never an open leg)
    closed = [l for l in leaves[0].legs if any(l in t.legs for t in leaves[1:])]
    legs = tuple(closed[:sliced])
    fused, report = fuse_stem_operands(leaves, pairs, legs)
    assert report["groups"] >= 1, "the network is stem-shaped: something fuses"
    assert len(fused) == len(pairs)
    tn = _network(leaves, arrays)
    if not sliced:
        before = build_program(tn, _replace(pairs))
        after = build_program(tn, _replace(fused))
        want = _by_leg(before, NumpyBackend().execute(before, arrays))
        got = _by_leg(after, NumpyBackend().execute(after, arrays))
        assert want.shape == (2,) * open_legs
        assert _close(got, want) < 1e-12
        return
    slicing = Slicing(legs, (2,) * len(legs))
    before = build_sliced_program(tn, _replace(pairs), slicing)
    after = build_sliced_program(tn, _replace(fused), slicing)
    total_want = total_got = 0.0
    for s in range(slicing.num_slices):
        want = _by_leg(before.program, execute_sliced_numpy(before, arrays, slice_range=(s, s + 1)))
        got = _by_leg(after.program, execute_sliced_numpy(after, arrays, slice_range=(s, s + 1)))
        assert _close(got, want) < 1e-12, f"slice {s}"
        total_want, total_got = total_want + want, total_got + got
    whole = _by_leg(after.program, execute_sliced_numpy(after, arrays))
    assert _close(whole, total_want) < 1e-12
    assert _close(total_got, total_want) < 1e-12


def _stem_and(*smalls, wires=18):
    """A stem of ``wires`` legs ``0..wires-1`` and small tensors given by
    their legs, applied in order."""
    leaves = [LeafTensor(list(range(wires)), [2] * wires)]
    leaves += [LeafTensor(list(legs), [2] * len(legs)) for legs in smalls]
    n = len(leaves)
    pairs = [(0, 1)] + [(n + i - 1, i + 1) for i in range(1, n - 1)]
    return leaves, pairs


def _large_steps(leaves, pairs):
    """``(k, n)`` of every step of a path whose larger operand holds
    ``STEM_MIN`` elements or more."""
    legs = [frozenset(t.legs) for t in leaves]
    out = []
    for a, b in pairs:
        big, small = (a, b) if len(legs[a]) >= len(legs[b]) else (b, a)
        if 2 ** len(legs[big]) >= STEM_MIN:
            shared = legs[a] & legs[b]
            out.append((2 ** len(shared), 2 ** len(legs[small] - shared)))
        legs.append(legs[a] ^ legs[b])
    return out


BOUNDS = {
    # two one-leg gates, then two two-leg gates: one group of four
    "a run joins": (dict(smalls=[(0, 100), (1, 101), (2, 3, 102, 103), (4, 5, 104, 105)]),
                    dict(groups=1, large=[4, 1], fused_kn=[(64, 64)])),
    # W2 acts on the leg W1 made: it is contracted between them
    "legs two smalls share": (dict(smalls=[(0, 100), (100, 101), (101, 102)]),
                              dict(groups=1, large=[3, 1], fused_kn=[(2, 2)])),
    # seven legs contracted in all: the seventh would make 2k = 256
    "never 2k over 128": (dict(smalls=[(0, 1, 2), (3, 4, 5), (6,)], wires=24),
                          dict(groups=1, large=[3, 2], fused_kn=[(64, 1), (2, 1)])),
    # the joined tensor would add 2^7
    "never n over the cap": (dict(smalls=[(0, 100, 101, 102), (1, 103, 104, 105), (2, 106)]),
                             dict(groups=1, large=[3, 2], fused_kn=[(4, 64), (2, 2)])),
    # a stem of 2^17 elements: the plan is what it was
    "nothing under 2^18": (dict(smalls=[(0, 100), (1, 101), (2, 102)], wires=17),
                           dict(groups=0, large=[0, 0], fused_kn=[])),
    # a gauss step (k = 2^7) in the middle: left alone, and it ends the run
    "a gauss step": (dict(smalls=[(0, 100), (1, 101), tuple(range(2, 9)), (9, 102), (10, 103)],
                          wires=27),
                     dict(groups=2, large=[5, 3], fused_kn=[(4, 4), (128, 1), (4, 4)])),
    # a step of two large values in the middle: left alone
    "two large values": (dict(smalls=[(0, 100), (1, 101), tuple(range(2, 18)) + tuple(range(200, 218)),
                                      (200, 300), (201, 301)]),
                         dict(groups=2, large=[5, 3], fused_kn=[(4, 4), None, (4, 4)])),
}


@pytest.mark.parametrize("case", list(BOUNDS))
def test_the_bounds_of_the_rule(case):
    spec, want = BOUNDS[case]
    leaves, pairs = _stem_and(*spec["smalls"], wires=spec.get("wires", 18))
    fused, report = fuse_stem_operands(leaves, pairs)
    assert report["groups"] == want["groups"]
    assert report["large_steps"] == want["large"]
    assert report["steps_removed"] == want["large"][0] - want["large"][1]
    assert len(fused) == len(pairs)
    if not want["groups"]:
        assert fused == pairs
    after = _large_steps(leaves, fused)
    assert len(after) == want["large"][1]
    for got, expected in zip(after, want["fused_kn"]):
        if expected is not None:
            assert got == expected
    # nothing the pass made is over the one-dot bound or the cap
    made = set(after) - set(_large_steps(leaves, pairs))
    assert all(2 * k <= BLOCK_MAX_CONTRACT and n <= JOINED_MAX for k, n in made)
    assert report["streamed_elems"][1] <= report["streamed_elems"][0]
    # the program the compiler builds of it: still one dot a fused step
    tn = CompositeTensor()
    for leaf in leaves:
        tn.push_tensor(leaf)
    ks = lambda p: sorted(step_dims(st)[1] for st in build_program(tn, _replace(p)).steps)
    assert max(ks(fused)) <= max(max(ks(pairs)), JOINED_MAX)


def test_hoisted_small_products_land_in_the_prelude():
    """A sliced plan: the small operands no sliced leg enters are
    hoisted, and so is their product; a slice runs one step for three."""
    leaves, pairs = _stem_and((2, 100), (3, 101), (4, 5, 102), wires=20)
    slicing = Slicing((0, 1), (2, 2))
    fused, report = fuse_stem_operands(leaves, pairs, slicing.legs)
    assert report["groups"] == 1 and report["large_steps"] == [3, 1]
    tn = CompositeTensor()
    for leaf in leaves:
        tn.push_tensor(leaf)
    before = hoist_sliced_program(build_sliced_program(tn, _replace(pairs), slicing))
    after = hoist_sliced_program(build_sliced_program(tn, _replace(fused), slicing))
    assert len(before.prelude_steps) == 0 and len(before.residual.program.steps) == 3
    assert len(after.prelude_steps) == 2 and len(after.residual.program.steps) == 1
    # in the sliced model the stem holds 2^18: with no leg sliced it is
    # the same run; with the stem's size taken unsliced a 2^17 stem fuses
    # nothing
    small, _ = _stem_and((2, 100), (3, 101), wires=19)
    assert fuse_stem_operands(small, [(0, 1), (3, 2)], (0, 1))[1]["groups"] == 0
    assert fuse_stem_operands(small, [(0, 1), (3, 2)])[1]["groups"] == 1


@pytest.fixture
def registry():
    reg = obs.MetricsRegistry()
    prior = obs.get_registry()
    was = obs.enabled()
    obs.configure(enabled=True, registry=reg)
    yield reg
    obs.configure(enabled=was, registry=prior)


def _sliced_plan(fuse: bool):
    from tnc_tpu.builders.sycamore_circuit import sycamore_circuit
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.tensornetwork.simplify import simplify_network

    raw, _ = sycamore_circuit(30, 12, np.random.default_rng(5)).into_amplitude_network("0" * 30)
    tn = simplify_network(raw)
    result = Greedy(OptMethod.GREEDY).find_path(tn)
    pairs, slicing = slice_and_reconfigure(
        list(tn.tensors), result.ssa_path.toplevel, 2.0 ** 22,
        reconf_rounds=1, step_budget=None, final_rounds=2, final_budget=None,
        fuse=fuse,
    )
    return tn, pairs, slicing


def test_the_ranking_call_and_the_plan_handed_out_slice_alike(registry):
    """``fuse=False`` (the search's ranking) returns the search's own
    path; the default returns it re-associated: the same slicing, the
    same leaves, the same number of steps, the same sum, fewer large
    steps; the counter and the summary read what the pass did."""
    tn, plain, plain_slicing = _sliced_plan(False)
    assert plain_slicing.fusion is None
    assert not obs.counters_by_prefix("plan.stem_fusion")
    tn2, fused, slicing = _sliced_plan(True)
    assert slicing == plain_slicing and slicing.num_slices == plain_slicing.num_slices
    assert slicing.num_slices > 1
    assert [sorted(t.legs) for t in tn.tensors] == [sorted(t.legs) for t in tn2.tensors]
    assert len(fused) == len(plain)
    report = slicing.fusion
    assert report["groups"] >= 1, "a 2^22 budget leaves stems of 2^18 and more"
    n = len(tn.tensors)
    again, same = fuse_stem_operands(
        list(tn.tensors), replace_ssa_ordering(plain, n), slicing.legs
    )
    assert same == report
    assert list(_replace(again).toplevel) == [tuple(p) for p in fused]
    assert report["large_steps"][1] == report["large_steps"][0] - report["steps_removed"]
    assert report["streamed_elems"][1] < report["streamed_elems"][0]
    assert report["macs"][1] >= report["macs"][0]
    counters = obs.counters_by_prefix("plan.stem_fusion")
    assert counters["plan.stem_fusion{kind=groups}"] == 2 * report["groups"]
    assert counters["plan.stem_fusion{kind=steps_removed}"] == 2 * report["steps_removed"]

    sp_plain = build_sliced_program(tn, ContractionPath.simple(plain), plain_slicing)
    sp = build_sliced_program(tn, ContractionPath.simple(fused), slicing)
    # a note on the slicing is no part of a sliced program's identity
    assert (sp.signature_digest() == sp_plain.signature_digest()) == (fused == plain)
    assert Slicing.from_obj(slicing.to_obj()) == slicing
    summary = kernel_plan_summary(sp.program)["fusion"]
    assert summary == report
    assert kernel_plan_summary(hoist_sliced_program(sp).residual.program)["fusion"] == report
    assert kernel_plan_summary(sp_plain.program)["fusion"] is None
    arrays = [leaf.data.into_data() for leaf in tn.tensors]
    lo = 3
    want = execute_sliced_numpy(sp_plain, arrays, slice_range=(lo, lo + 2))
    got = execute_sliced_numpy(sp, arrays, slice_range=(lo, lo + 2))
    assert _close(got, want) < 1e-12


def test_a_sliced_batch_with_open_legs_still_comes_back_by_qubit():
    """Through the entry, under a budget: four open legs ride the stems,
    gates fuse onto them, the program's ``result_legs`` come in the
    program's order, and ``AmplitudeBatchProgram`` still returns axis
    ``j`` = ``open_qubits[j]`` (``to_host`` for a ``host=False`` result
    as the host's)."""
    from tnc_tpu.builders.sycamore_circuit import sycamore_circuit
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.queries.amplitude_batch import bind_amplitude_batch
    from tnc_tpu.tensornetwork.contraction import contract_tensor_network
    from tnc_tpu.tensornetwork.simplify import simplify_network

    n = 24
    circuit = sycamore_circuit(n, 14, np.random.default_rng(9))
    opened = tuple(int(q) for q in np.random.default_rng(2).permutation(n)[:4])
    prog = bind_amplitude_batch(circuit.copy(), opened, target_size=2.0 ** 20)
    assert prog.num_slices >= 16
    report = kernel_plan_summary(prog.bound.sliced.program)["fusion"]
    assert report is not None and report["groups"] >= 1
    assert report["large_steps"][1] < report["large_steps"][0]
    closed_bits = "".join("01"[b] for b in np.random.default_rng(3).integers(0, 2, n - 4))
    backend = NumpyBackend()
    got = prog.amplitudes(closed_bits, backend)
    assert got.shape == (2,) * 4
    half = prog.num_slices // 2
    halves = sum(
        prog.to_host(prog.amplitudes(closed_bits, backend, slice_range=r, host=False))
        for r in ((0, half), (half, prog.num_slices))
    )
    assert _close(halves, got) < 1e-12
    # each amplitude on its own, as a closed network at its own
    # bitstring: between them the four tell any two axes apart
    scale = float(np.abs(got).max())
    for index in ((0, 0, 0, 1), (0, 1, 1, 0), (1, 0, 1, 0), (1, 1, 0, 1)):
        bits = dict(zip(prog.closed_qubits, closed_bits))
        bits.update(zip(opened, (str(b) for b in index)))
        tn, _ = circuit.copy().into_amplitude_network("".join(bits[q] for q in range(n)))
        tn = simplify_network(tn)
        path = Greedy(OptMethod.GREEDY).find_path(tn).replace_path()
        want = complex(contract_tensor_network(tn, path, backend="numpy").data.into_data())
        assert abs(got[index] - want) < 1e-10 * scale, index


class _ChainFinder:
    """A pathfinder that answers with the chain it was given."""

    def __init__(self, pairs):
        self.pairs = pairs

    def find_path(self, tn):
        from tnc_tpu.contractionpath.contraction_cost import contract_path_cost
        from tnc_tpu.contractionpath.paths.base import BasicContractionPathResult

        ssa = ContractionPath.simple(self.pairs)
        flops, size = contract_path_cost(tn.tensors, ssa_replace_ordering(ssa), True)
        return BasicContractionPathResult(ssa, flops, size)


@pytest.mark.parametrize("seed", [5, 6])
def test_plan_structure_fuses_an_unsliced_plan(seed, registry):
    """The served template's branch: no budget, the finder's path is
    re-associated, the program carries the report and gives the value
    of the finder's own path, open legs and all."""
    from tnc_tpu.serve.rebind import plan_structure

    leaves, arrays, pairs = _stem_network(seed, 18, 12, 2)
    tn = _network(leaves, arrays)
    path, slicing, program, sliced, result = plan_structure(tn, _ChainFinder(pairs))
    assert slicing is None and sliced is None
    expected, report = fuse_stem_operands(leaves, pairs)
    assert report["groups"] >= 1
    assert program.fusion == report
    assert kernel_plan_summary(program)["fusion"] == report
    assert list(path.toplevel) == list(_replace(expected).toplevel)
    assert result.ssa_path.toplevel == pairs  # the finder's own, untouched
    counters = obs.counters_by_prefix("plan.stem_fusion")
    assert counters["plan.stem_fusion{kind=groups}"] == 2 * report["groups"]
    plain = build_program(tn, _replace(pairs))
    want = _by_leg(plain, NumpyBackend().execute(plain, arrays))
    got = _by_leg(program, NumpyBackend().execute(program, arrays))
    assert _close(got, want) < 1e-12
    # a plan with no 2^18 value comes back as the finder left it
    leaves, arrays, pairs = _stem_network(seed, 12, 8, 2)
    tn = _network(leaves, arrays)
    path, _, program, _, result = plan_structure(tn, _ChainFinder(pairs))
    assert list(path.toplevel) == list(result.replace_path().toplevel)
    assert program.fusion["groups"] == 0
