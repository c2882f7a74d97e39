"""Targeted tests for branches the main suites skip:
multilevel coarsening in the Python bisection oracle (graphs above the
coarsen_to threshold), the pure-Python k-way fallback behind the native
partitioner, and the genetic optimizer's spawn-pool fitness path (dark
on this 1-CPU sandbox without the worker override).
"""

import random

import numpy as np


from tnc_tpu.partitioning.bisect import Hypergraph, bisect, partition_kway


def _random_hypergraph(n: int, seed: int) -> Hypergraph:
    """Connected hypergraph: a vertex chain plus random small hyperedges
    (the shape tensor-network line graphs take)."""
    rng = random.Random(seed)
    pins = [[i, i + 1] for i in range(n - 1)]
    weights = [1.0 + rng.random() for _ in pins]
    for _ in range(n):
        k = rng.randint(2, 4)
        e = rng.sample(range(n), k)
        pins.append(e)
        weights.append(rng.random())
    return Hypergraph(n, [1.0] * n, pins, weights)


def _cut_weight(hg: Hypergraph, part) -> float:
    return sum(
        w
        for pins, w in zip(hg.edge_pins, hg.edge_weights)
        if len({part[v] for v in pins}) > 1
    )


def test_bisect_multilevel_coarsens_large_graph():
    """300 vertices > coarsen_to=80 forces the heavy-edge-matching
    coarsening + uncoarsen/refine phases to execute."""
    hg = _random_hypergraph(300, seed=9)
    part = bisect(hg, imbalance=0.1, rng=random.Random(1))
    assert len(part) == 300 and set(part) <= {0, 1}
    sizes = [part.count(0), part.count(1)]
    assert min(sizes) > 0
    # balance: each side within (1+imbalance) x half the total weight
    assert max(sizes) <= (1 + 0.1) * 150 + 1
    # sanity: the refined cut beats an alternating-assignment cut
    naive = [v % 2 for v in range(300)]
    assert _cut_weight(hg, part) < _cut_weight(hg, naive)


def test_partition_kway_python_fallback(monkeypatch):
    """With the native partitioner unavailable, the recursive-bisection
    Python fallback must produce a valid, reasonably balanced k-way
    partition."""
    import tnc_tpu.partitioning.native_binding as nb

    monkeypatch.setattr(nb, "native_partition_kway", lambda *a, **k: None)
    hg = _random_hypergraph(120, seed=3)
    part = partition_kway(hg, k=4, rng=random.Random(7))
    assert len(part) == 120
    assert set(part) == {0, 1, 2, 3}
    sizes = [part.count(b) for b in range(4)]
    assert min(sizes) > 0
    assert max(sizes) <= 2 * (120 // 4)


def test_genetic_pool_fitness_path(monkeypatch):
    """TNC_TPU_SA_WORKERS=2 forces the spawn-pool fitness evaluation
    (the reference's ``with_par_fitness`` analogue); results must match
    the inline path's contract (valid chromosome, score no worse than
    the initial partitioning)."""
    monkeypatch.setenv("TNC_TPU_SA_WORKERS", "2")
    from tnc_tpu.contractionpath.repartitioning import genetic as genetic_mod
    from tnc_tpu.builders.random_circuit import random_circuit
    from tnc_tpu.builders.connectivity import ConnectivityLayout
    from tnc_tpu.contractionpath.communication_schemes import CommunicationScheme
    from tnc_tpu.contractionpath.repartitioning.genetic import (
        GeneticSettings,
        balance_partitions,
    )
    from tnc_tpu.contractionpath.repartitioning.simulated_annealing import (
        evaluate_partitioning,
    )
    from tnc_tpu.tensornetwork.partitioning import find_partitioning
    from tnc_tpu.tensornetwork.simplify import simplify_network

    rng_np = np.random.default_rng(0)
    tn = simplify_network(
        random_circuit(
            10, 6, 0.4, 0.4, rng_np, ConnectivityLayout.LINE, bitstring="0" * 10
        )
    )
    initial = find_partitioning(tn, 3)
    rng = random.Random(5)
    score0 = evaluate_partitioning(
        tn, initial, CommunicationScheme.GREEDY, None, random.Random(5)
    )
    # the point of this test is the POOL path: fail loudly if it silently
    # degrades to inline evaluation (pool creation returning None)
    made = []
    mapped = []
    orig_make = genetic_mod._make_fitness_pool

    def spying_make(*args, **kwargs):
        pool = orig_make(*args, **kwargs)
        made.append(pool)
        if pool is not None:
            orig_map = pool.map_async

            def spying_map(*a, **k):
                res = orig_map(*a, **k)
                mapped.append(res)
                return res

            pool.map_async = spying_map
        return pool

    monkeypatch.setattr(genetic_mod, "_make_fitness_pool", spying_make)
    best, best_score = balance_partitions(
        tn,
        initial,
        3,
        rng,
        settings=GeneticSettings(
            population_size=4, max_generations=2, stale_limit=2
        ),
    )
    assert len(best) == len(tn)
    assert best_score <= score0
    assert made and made[0] is not None, "spawn pool was not created"
    # every generation scored through the pool: map_async was used and
    # each call delivered (an exception would have nulled the pool and
    # silently fallen back to inline evaluation)
    assert mapped, "pool.map_async never ran"
    assert all(r.successful() for r in mapped)
