"""km1 (connectivity) partitioning objective + custom-config escape hatch.

The reference embeds two distinct KaHyPar configs — cut vs km1 — plus a
``Custom(path)`` variant (``tnc/src/tensornetwork/partition_config.rs:
12-36``, selected at ``partitioning.rs:40-55``). These tests pin down
that the two presets here are *actually different objectives*: km1 refinement strictly improves the connectivity metric
on a fixture where cut and km1 disagree, the Python and native
refinements agree on the metric they optimize, and the config object
overrides presets.
"""

import random

import numpy as np
import pytest

from tnc_tpu.partitioning.bisect import kway_refine_km1, partition_kway
from tnc_tpu.partitioning.hypergraph import Hypergraph
from tnc_tpu.tensornetwork.partitioning import (
    PartitionConfig,
    PartitioningStrategy,
    find_partitioning,
)
from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor


def _scatter_fixture() -> tuple[Hypergraph, list[int]]:
    """4 blocks of 3 vertices; one heavy hyperedge pinned in every block
    plus one light 'magnet' vertex-pair edge. A cut objective cannot save
    the heavy edge (it stays cut either way, weight counted once), but
    km1 pays (lambda-1): pulling the heavy edge's pins together across
    fewer blocks is a km1-only gain."""
    # vertices 0-11; blocks of 3 by construction
    edges: list[list[int]] = []
    weights: list[float] = []
    # chain edges keeping each intended block loosely together
    for b in range(4):
        base = 3 * b
        edges += [[base, base + 1], [base + 1, base + 2]]
        weights += [1.0, 1.0]
    # heavy hyperedge touching one vertex of each block
    edges.append([2, 5, 8, 11])
    weights.append(10.0)
    part = [b for b in range(4) for _ in range(3)]
    hg = Hypergraph(12, [1.0] * 12, edges, weights)
    return hg, part


def test_km1_and_cut_disagree_on_fixture():
    hg, part = _scatter_fixture()
    # the heavy edge spans 4 blocks: cut counts it once (10), km1 thrice
    assert hg.cut_weight(part) == pytest.approx(10.0)
    assert hg.km1_weight(part) == pytest.approx(30.0)


def test_kway_refine_km1_improves_connectivity():
    hg, part = _scatter_fixture()
    before = hg.km1_weight(part)
    refined = list(part)
    # generous imbalance so the refiner may regroup the heavy edge's pins
    kway_refine_km1(hg, refined, 4, imbalance=1.5)
    after = hg.km1_weight(refined)
    assert after < before  # strict: the km1 pass found connectivity gains
    assert sorted(set(refined)) <= list(range(4))


def test_native_and_python_km1_refinement_agree(monkeypatch):
    from tnc_tpu.partitioning.native_binding import (
        native_km1_weight,
        native_kway_refine_km1,
    )

    hg, part = _scatter_fixture()
    native = native_kway_refine_km1(hg, list(part), 4, 1.5)
    if native is None:
        pytest.skip("native partitioner unavailable")
    python = list(part)
    kway_refine_km1(hg, python, 4, imbalance=1.5)
    # same metric value (move order may differ; the objective must not)
    assert hg.km1_weight(native) == pytest.approx(hg.km1_weight(python))
    assert hg.km1_weight(native) < hg.km1_weight(part)
    # the native metric agrees with the Python one (and rejects invalid
    # partitions instead of reading past its seen[k] buffer)
    assert native_km1_weight(hg, native, 4) == pytest.approx(
        hg.km1_weight(native)
    )
    assert native_km1_weight(hg, [0, 7] + [0] * 10, 4) is None


@pytest.mark.parametrize("use_native", [False, True])
def test_partition_kway_objectives_diverge(monkeypatch, use_native):
    if not use_native:
        monkeypatch.setenv("TNC_TPU_NO_NATIVE", "1")
    rng = np.random.default_rng(3)
    # random hypergraph with several wide hyperedges: enough scatter for
    # the km1 pass to have real work at k=4
    n = 40
    edges = []
    weights = []
    for _ in range(30):
        size = int(rng.integers(2, 6))
        pins = sorted(rng.choice(n, size=size, replace=False).tolist())
        edges.append(pins)
        weights.append(float(rng.integers(1, 10)))
    hg = Hypergraph(n, [1.0] * n, edges, weights)

    cut_part = partition_kway(hg, 4, 0.2, random.Random(5), objective="cut")
    km1_part = partition_kway(hg, 4, 0.2, random.Random(5), objective="km1")
    # km1 preset must be at least as good on its own metric, and on a
    # scatter-heavy instance strictly better than the cut preset
    assert hg.km1_weight(km1_part) <= hg.km1_weight(cut_part)

    with pytest.raises(ValueError):
        partition_kway(hg, 4, 0.2, random.Random(5), objective="bogus")


def _line_network(n=12) -> CompositeTensor:
    return CompositeTensor(
        [LeafTensor.from_const([i, i + 1], 4) for i in range(n)]
    )


def test_find_partitioning_strategies_and_config():
    tn = _line_network()
    cut = find_partitioning(
        tn, 3, strategy=PartitioningStrategy.MIN_CUT, seed=9
    )
    km1 = find_partitioning(
        tn, 3, strategy=PartitioningStrategy.COMMUNITY_FINDING, seed=9
    )
    assert len(cut) == len(km1) == len(tn)
    assert set(cut) <= {0, 1, 2} and set(km1) <= {0, 1, 2}

    # the Custom escape hatch: a config object overrides the preset
    custom = find_partitioning(
        tn,
        3,
        config=PartitionConfig(
            objective="km1", imbalance=0.25, seed=123, unit_vertex_weights=True
        ),
    )
    assert len(custom) == len(tn)
    assert set(custom) <= {0, 1, 2}
