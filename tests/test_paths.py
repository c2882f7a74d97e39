"""Pathfinders against the reference's exact fixtures
(``tnc/src/contractionpath/paths/cotengrust.rs:158-307``).
"""

from tnc_tpu import CompositeTensor, LeafTensor, path
from tnc_tpu.contractionpath.contraction_path import (
    ssa_ordering,
    ssa_replace_ordering,
    validate_path,
)
from tnc_tpu.contractionpath.paths import Greedy, Optimal, OptMethod


def setup_simple():
    bd = {0: 5, 1: 2, 2: 6, 3: 8, 4: 1, 5: 3, 6: 4}
    return CompositeTensor(
        [
            LeafTensor.from_map([4, 3, 2], bd),
            LeafTensor.from_map([0, 1, 3, 2], bd),
            LeafTensor.from_map([4, 5, 6], bd),
        ]
    )


def setup_complex():
    bd = {
        0: 27, 1: 18, 2: 12, 3: 15, 4: 5, 5: 3,
        6: 18, 7: 22, 8: 45, 9: 65, 10: 5, 11: 17,
    }
    return CompositeTensor(
        [
            LeafTensor.from_map([4, 3, 2], bd),
            LeafTensor.from_map([0, 1, 3, 2], bd),
            LeafTensor.from_map([4, 5, 6], bd),
            LeafTensor.from_map([6, 8, 9], bd),
            LeafTensor.from_map([10, 8, 9], bd),
            LeafTensor.from_map([5, 1, 0], bd),
        ]
    )


def test_greedy_simple():
    result = Greedy(OptMethod.GREEDY).find_path(setup_simple())
    assert result.ssa_path == path((0, 1), (3, 2))
    assert result.flops == 600.0
    assert result.size == 538.0


def test_greedy_simple_inner():
    bd = {0: 5, 1: 2, 2: 6, 3: 8, 4: 1, 5: 3, 6: 4}
    tn = CompositeTensor(
        [
            LeafTensor.from_map([4, 3, 2], bd),
            LeafTensor.from_map([4, 3, 2], bd),
            LeafTensor.from_map([0, 1, 5], bd),
            LeafTensor.from_map([1, 6], bd),
        ]
    )
    result = Greedy(OptMethod.GREEDY).find_path(tn)
    assert result.ssa_path == path((0, 1), (2, 3), (4, 5))
    assert result.flops == 228.0
    assert result.size == 121.0


def test_greedy_simple_outer():
    bd = {0: 3, 1: 2, 2: 2}
    tn = CompositeTensor(
        [
            LeafTensor.from_map([0], bd),
            LeafTensor.from_map([1], bd),
            LeafTensor.from_map([2], bd),
        ]
    )
    result = Greedy(OptMethod.GREEDY).find_path(tn)
    assert result.ssa_path == path((2, 1), (0, 3))
    assert result.flops == 16.0
    assert result.size == 19.0


def test_greedy_complex_outer():
    bd = {0: 5, 1: 4}
    tn = CompositeTensor(
        [
            LeafTensor.from_map([0], bd),
            LeafTensor.from_map([0], bd),
            LeafTensor.from_map([1], bd),
            LeafTensor.from_map([1], bd),
        ]
    )
    result = Greedy(OptMethod.GREEDY).find_path(tn)
    assert result.ssa_path == path((0, 1), (2, 3), (5, 4))
    assert result.flops == 10.0
    assert result.size == 11.0


def test_greedy_complex():
    result = Greedy(OptMethod.GREEDY).find_path(setup_complex())
    assert result.ssa_path == path((1, 5), (3, 4), (6, 0), (7, 2), (9, 8))
    assert result.flops == 529815.0
    assert result.size == 89478.0


def test_greedy_nested():
    bd = {0: 5, 1: 2, 2: 6, 3: 8, 4: 1, 5: 3, 6: 4}
    inner = CompositeTensor(
        [LeafTensor.from_map([4, 3, 2], bd), LeafTensor.from_map([0, 1, 3, 2], bd)]
    )
    tn = CompositeTensor([inner, LeafTensor.from_map([4, 5, 6], bd)])
    result = Greedy(OptMethod.GREEDY).find_path(tn)
    assert 0 in result.ssa_path.nested
    assert result.ssa_path.nested[0].toplevel == [(0, 1)]
    assert result.ssa_path.toplevel == [(0, 1)]
    assert result.flops == 600.0
    assert result.size == 538.0


def test_random_greedy_validates():
    tn = setup_complex()
    result = Greedy(OptMethod.RANDOM_GREEDY, ntrials=8).find_path(tn)
    replace = result.replace_path()
    assert validate_path(replace, len(tn))
    # Deterministic with a fixed seed.
    again = Greedy(OptMethod.RANDOM_GREEDY, ntrials=8).find_path(tn)
    assert again.ssa_path == result.ssa_path


def test_optimal_not_worse_than_greedy():
    tn = setup_complex()
    greedy = Greedy(OptMethod.GREEDY).find_path(tn)
    optimal = Optimal().find_path(tn)
    assert optimal.flops <= greedy.flops
    assert validate_path(optimal.replace_path(), len(tn))


def test_optimal_simple_matches_greedy_costs():
    result = Optimal().find_path(setup_simple())
    assert result.flops == 600.0


def test_ssa_ordering():
    # Optimizer triples with arbitrary intermediate ids -> strict SSA.
    triples = [(0, 1, 7), (7, 2, 9)]
    p = ssa_ordering(triples, 3)
    assert p.toplevel == [(0, 1), (3, 2)]


def test_ssa_replace_ordering():
    ssa = path((0, 1), (3, 2))
    replace = ssa_replace_ordering(ssa)
    assert replace.toplevel == [(0, 1), (0, 2)]

    ssa2 = path((0, 1), (2, 3), (4, 5))
    replace2 = ssa_replace_ordering(ssa2)
    assert replace2.toplevel == [(0, 1), (2, 3), (0, 2)]


def test_validate_path():
    good = ssa_replace_ordering(path((0, 1), (0, 2)))
    assert validate_path(good, 3)
    bad = path((0, 1), (1, 2))  # uses consumed tensor 1
    assert not validate_path(bad, 3)


def test_hyper_trials_parallel_matches_serial(monkeypatch):
    """The spawn-pool trial runner must reproduce the
    serial winner exactly — trial t always draws from Random(seed+t) and
    results merge by trial index, so worker count cannot change the
    outcome."""
    import numpy as np

    from tnc_tpu.builders.connectivity import ConnectivityLayout
    from tnc_tpu.builders.random_circuit import random_circuit
    from tnc_tpu.contractionpath.paths.hyper import Hyperoptimizer

    rng = np.random.default_rng(21)
    tn = random_circuit(
        16, 8, 0.4, 0.4, rng, ConnectivityLayout.SYCAMORE, bitstring="0" * 16
    )
    opt = dict(ntrials=6, seed=3, polish_rounds=0, reconfigure_rounds=1)

    monkeypatch.setenv("TNC_TPU_HYPER_WORKERS", "1")
    serial = Hyperoptimizer(**opt).find_path(tn)
    monkeypatch.setenv("TNC_TPU_HYPER_WORKERS", "2")
    parallel = Hyperoptimizer(**opt).find_path(tn)

    assert serial.ssa_path.toplevel == parallel.ssa_path.toplevel
    assert serial.flops == parallel.flops
