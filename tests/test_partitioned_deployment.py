"""The partition-parallel pipeline as the benchmark's four-chip cell
deploys it (``perf/configs/sycamore30_m14_part4.json``), at a small size
on the virtual CPU mesh: the example's recipe — ``find_partitioning(tn,
4)``, ``partition_tensor_network``, ``Greedy`` paths,
``distributed_partitioned_contraction`` with its defaults — against a
plain pairwise complex128 contraction of the same leaves, the names its
programs carry into a trace, the counts a call leaves always-on, and
which partitioner the cell gets."""

import warnings

import numpy as np
import pytest

from tnc_tpu import CompositeTensor, obs
from tnc_tpu.builders.sycamore_circuit import sycamore_circuit
from tnc_tpu.contractionpath.paths import Greedy, OptMethod
from tnc_tpu.ops.program import flat_leaf_tensors
from tnc_tpu.parallel import distributed_partitioned_contraction, partitioned
from tnc_tpu.tensornetwork.partitioning import (
    find_partitioning,
    partition_tensor_network,
)
from tnc_tpu.tensornetwork.simplify import simplify_network


def _cell_network(qubits=14, cycles=8, seed=42):
    """The cell's structure at a small size: seeded gates, one
    bitstring's amplitude network, simplified."""
    rng = np.random.default_rng(seed)
    bits = "".join("01"[b] for b in rng.integers(0, 2, size=qubits))
    raw, _ = sycamore_circuit(qubits, cycles, rng).into_amplitude_network(bits)
    return simplify_network(raw)


def _example_plan(tn):
    partitioning = find_partitioning(tn, 4)
    grouped = partition_tensor_network(
        CompositeTensor(list(tn.tensors)), partitioning
    )
    return grouped, Greedy(OptMethod.GREEDY).find_path(grouped).replace_path()


def _plain_complex128(tn) -> complex:
    """Pairwise ``np.tensordot`` in complex128 over the leaves, always
    the first tensor with the first that shares a leg with it."""
    todo = [
        (list(leaf.legs), np.asarray(leaf.data.into_data(), dtype=np.complex128))
        for leaf in flat_leaf_tensors(tn)
    ]
    legs, data = todo.pop(0)
    while todo:
        j = next(
            (i for i, (l, _) in enumerate(todo) if set(l) & set(legs)), 0
        )
        other_legs, other = todo.pop(j)
        shared = [l for l in legs if l in other_legs]
        data = np.tensordot(
            data, other,
            axes=([legs.index(l) for l in shared],
                  [other_legs.index(l) for l in shared]),
        )
        legs = [l for l in legs if l not in shared] + [
            l for l in other_legs if l not in shared
        ]
    assert legs == []
    return complex(data)


@pytest.mark.parametrize("split_complex", [False, True], ids=["complex", "split"])
def test_example_plan_equals_plain_contraction_and_names_its_programs(
    split_complex, monkeypatch
):
    import jax

    tn = _cell_network()
    grouped, path = _example_plan(tn)
    assert len(grouped.tensors) == 4 and len(path.toplevel) == 3

    lowered: list[tuple[str, str]] = []  # (role, head of the lowered module)
    real = partitioned.jit_program

    def spying(program, *args, role=None, **kw):
        fn = real(program, *args, role=role, **kw)

        def run(buffers):
            specs = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), list(buffers)
            )
            with warnings.catch_warnings():  # tiny leaves cannot be donated
                warnings.simplefilter("ignore")
                lowered.append((role, fn.jitted.lower(specs).as_text()[:400]))
            return fn(buffers)

        return run

    monkeypatch.setattr(partitioned, "jit_program", spying)
    with obs.collect_phases() as totals:
        out = distributed_partitioned_contraction(
            grouped, path, n_devices=4, split_complex=split_complex
        )
    got = complex(np.asarray(out.data.into_data()).reshape(-1)[0])
    want = _plain_complex128(tn)
    assert abs(got - want) <= 2e-5 * max(abs(want), 2.0**-7)

    # four local programs, three pair programs, each lowered under its role
    assert sorted(role for role, _ in lowered) == (
        ["fanin_pair"] * 3 + ["partition_local"] * 4
    )
    for role, text in lowered:
        assert f"module @jit_tnc_{role}" in text, (role, text)

    # a call's counts, with nothing tracing
    assert totals["partitioned.fanin.pairs"] == 3
    assert 2 <= totals["partitioned.fanin.levels"] <= 3
    moved = totals["partitioned.fanin.bytes"]
    assert moved == totals["partitioned.fanin_level.bytes"] > 0
    assert totals["partitioned.fanin.flops"] > 0
    assert (
        totals["partitioned.local.cmacs_max"]
        >= totals["partitioned.local.cmacs_mean"]
        > 0
    )
    assert totals["partitioned.fetch.bytes"] == 8  # one complex64 amplitude
    for phase in ("scatter", "local", "fanin", "fetch"):
        assert totals[f"partitioned.{phase}"] > 0.0  # seconds


def test_role_is_part_of_the_program_cache_key():
    """One program under two roles is two executables with two names;
    without a role it keeps the name accepted metrics read."""
    from tnc_tpu.ops.backends import jit_program
    from tnc_tpu.ops.program import build_program

    tn = _cell_network(qubits=8, cycles=4)
    program = build_program(
        tn, Greedy(OptMethod.GREEDY).find_path(tn).replace_path()
    )
    plain = jit_program(program, False, donate=False)
    local = jit_program(program, False, donate=False, role="partition_local")
    pair = jit_program(program, False, donate=False, role="fanin_pair")
    assert len({id(plain), id(local), id(pair)}) == 3
    assert jit_program(program, False, donate=False, role="fanin_pair") is pair
    names = [fn.jitted.__name__ for fn in (plain, local, pair)]
    assert names == ["tnc_program", "tnc_partition_local", "tnc_fanin_pair"]


def test_hbm_budget_default_comes_from_the_device(monkeypatch):
    """``hbm_bytes=None`` asks the device: with a budget that nothing
    fits, the partitions are sliced locally and the amplitude stands."""
    tn = _cell_network(qubits=12, cycles=6)
    grouped, path = _example_plan(tn)
    want = _plain_complex128(tn)
    asked = []
    real = partitioned._slice_partition

    def spy(child, nested, program, hbm_bytes):
        asked.append(hbm_bytes)
        return real(child, nested, program, hbm_bytes)

    monkeypatch.setattr(partitioned, "_slice_partition", spy)
    out = distributed_partitioned_contraction(grouped, path, n_devices=4)
    got = complex(np.asarray(out.data.into_data()).reshape(-1)[0])
    assert abs(got - want) <= 2e-5 * max(abs(want), 2.0**-6)
    from tnc_tpu.ops.budget import device_hbm_bytes

    assert asked == [device_hbm_bytes()] * 4


def test_which_partitioner_the_cell_gets(monkeypatch):
    """``find_partitioning(tn, 4)`` on the cell's structure: the native
    partitioner (four seeded multi-starts, the best cut kept) and the
    Python fallback (one start) give DIFFERENT partitionings, both
    balanced. The cell gets the native one — it is built in set-up on the
    chip's host, and the benchmark's own test pins the plan's digest."""
    from tnc_tpu.partitioning.native_binding import load_native

    if load_native() is None:
        pytest.skip("no native partitioner can be built here")
    tn = _cell_network(qubits=16, cycles=8)

    def cut(blocks) -> int:
        seen: dict[int, set] = {}
        for tensor, block in zip(tn.tensors, blocks):
            for leg in tensor.legs:
                seen.setdefault(leg, set()).add(block)
        return sum(1 for owners in seen.values() if len(owners) > 1)

    native = list(find_partitioning(tn, 4))
    assert native == list(find_partitioning(tn, 4))  # seeded: the same again
    monkeypatch.setenv("TNC_TPU_NO_NATIVE", "1")
    fallback = list(find_partitioning(tn, 4))
    monkeypatch.delenv("TNC_TPU_NO_NATIVE")
    n = len(tn.tensors)
    for blocks in (native, fallback):
        sizes = [blocks.count(b) for b in range(4)]
        assert sum(sizes) == n and max(sizes) - min(sizes) <= 1
    assert native != fallback
    assert cut(native) <= cut(fallback)
