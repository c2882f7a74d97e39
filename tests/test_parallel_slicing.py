"""Slice-parallel planning helpers (round 5).

``find_parallel_slicing`` (device-divisible slice sets), the benchmark's
execution-faithful rank gate for budget-missing plans, and the SPMD
executable cache that keeps compilation out of timed probe regions.
"""

import random as pyrandom

import numpy as np
import pytest

from tnc_tpu.builders.connectivity import ConnectivityLayout
from tnc_tpu.builders.random_circuit import random_circuit
from tnc_tpu.contractionpath.paths import Greedy, OptMethod
from tnc_tpu.contractionpath.slicing import (
    find_parallel_slicing,
    find_slicing,
    sliced_flops,
)
from tnc_tpu.tensornetwork.simplify import simplify_network


def _instance(seed=4, qubits=16, depth=8):
    rng = np.random.default_rng(seed)
    tn = simplify_network(
        random_circuit(
            qubits, depth, 0.5, 0.5, rng, ConnectivityLayout.SYCAMORE,
            bitstring="0" * qubits,
        )
    )
    result = Greedy(OptMethod.GREEDY).find_path(tn)
    return tn, result


@pytest.mark.parametrize("n_devices", [2, 4, 8])
def test_divisible_and_at_least_n(n_devices):
    tn, result = _instance()
    replace = result.replace_path().toplevel
    sl = find_parallel_slicing(list(tn.tensors), replace, n_devices)
    assert sl is not None
    assert sl.num_slices >= n_devices
    assert sl.num_slices % n_devices == 0


def test_target_size_respected():
    tn, result = _instance()
    replace = result.replace_path().toplevel
    target = result.size / 4.0
    sl = find_parallel_slicing(
        list(tn.tensors), replace, 4, target_size=target
    )
    assert sl is not None
    # must include at least the memory slicing find_slicing would pick
    base = find_slicing(list(tn.tensors), replace, target)
    assert set(base.legs) <= set(sl.legs)


def test_extra_legs_minimize_total_flops():
    """The divisibility legs are chosen by total sliced flops, so the
    parallel slicing never costs more than naively extending with the
    lexicographically-first closed legs."""
    tn, result = _instance()
    replace = result.replace_path().toplevel
    sl = find_parallel_slicing(list(tn.tensors), replace, 8)
    assert sl is not None
    tot = sliced_flops(list(tn.tensors), replace, sl)
    assert tot > 0
    # overhead is bounded: parallel slicing of this instance stays
    # within 32x of the serial plan (measured ~2-4x; the bound is slack
    # so seed drift cannot flake the suite)
    assert tot <= 32 * result.flops


def test_rank_solution_gates_budget_missing_plans():
    """A plan whose global slicing cannot reach the modeled budget must
    rank unplaceable (the 53q OOM class)."""
    import os
    import sys

    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    from bench import _rank_solution
    from tnc_tpu.contractionpath.repartitioning import compute_solution

    tn, _ = _instance()
    solution = compute_solution(
        tn, [i % 2 for i in range(len(tn.tensors))], rng=pyrandom.Random(0)
    )
    feasible_rank, _ = _rank_solution(solution, hbm=64 * 2**30)
    assert feasible_rank[0] != float("inf")
    # an absurd 1-byte budget cannot be reached by any slicing
    infeasible_rank, _ = _rank_solution(solution, hbm=1)
    assert infeasible_rank == (float("inf"), float("inf"))


def test_spmd_fn_cache_reuses_executable():
    from tnc_tpu.parallel.sliced_parallel import (
        _SPMD_FN_CACHE,
        distributed_sliced_contraction,
    )

    tn, result = _instance(qubits=10, depth=4)
    replace = result.replace_path()
    sl = find_parallel_slicing(
        list(tn.tensors), replace.toplevel, 2, target_size=result.size / 2
    )
    if sl is None:
        pytest.skip("instance did not slice")
    _SPMD_FN_CACHE.clear()
    distributed_sliced_contraction(tn, replace, sl, n_devices=2)
    assert len(_SPMD_FN_CACHE) == 1
    distributed_sliced_contraction(tn, replace, sl, n_devices=2)
    assert len(_SPMD_FN_CACHE) == 1  # same chunk: cache hit, no retrace
    distributed_sliced_contraction(
        tn, replace, sl, n_devices=2, max_slices=2
    )
    assert len(_SPMD_FN_CACHE) == 2  # different chunk: new executable
