"""Slice-invariant stem hoisting (`tnc_tpu.ops.hoist`).

Parity discipline: the *unhoisted numpy oracle* is law. Every hoisted
executor — numpy, chunked, the SPMD loop on the virtual mesh (one device
and two, complex + split) — must reproduce it; the hoist pass must degrade to a no-op
when every input touches a sliced leg; and the planner's hoist-aware
flop accounting must stay consistent with the naive totals.
"""

import numpy as np
import pytest

from tnc_tpu.contractionpath.contraction_path import ContractionPath
from tnc_tpu.contractionpath.slicing import (
    Slicing,
    StemAccountant,
    hoisted_sliced_flops,
    sliced_flops,
)
from tnc_tpu.ops.hoist import (
    hoist_sliced_program,
    hoist_step_flops,
    run_prelude,
)
from tnc_tpu.ops.sliced import (
    build_sliced_program,
    execute_sliced_numpy,
    execute_sliced_numpy_parallel,
    sliced_partials_numpy,
)
from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor
from tnc_tpu.tensornetwork.tensordata import TensorData


def _leaf(rng, legs, d=4):
    shape = [d] * len(legs)
    data = rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
    return LeafTensor(legs, shape, TensorData.matrix(data))


def _ring(seed=0, n=6, d=4):
    """Ring of n matrices; slicing a late leg leaves an invariant stem
    (the early contractions touch no sliced leg)."""
    rng = np.random.default_rng(seed)
    ts = [_leaf(rng, [i, (i + 1) % n], d) for i in range(n)]
    tn = CompositeTensor([t.copy() for t in ts])
    path = ContractionPath.simple([(0, i) for i in range(1, n)])
    return ts, tn, path


def _sliced(seed=0, legs=(3,), dims=(4,)):
    ts, tn, path = _ring(seed)
    sp = build_sliced_program(tn, path, Slicing(tuple(legs), tuple(dims)))
    arrays = [t.data.into_data() for t in ts]
    return sp, arrays


def test_split_is_exhaustive_and_disjoint():
    sp, _ = _sliced()
    hp = hoist_sliced_program(sp)
    assert not hp.is_noop
    assert len(hp.prelude_steps) >= 1
    assert len(hp.prelude_steps) + len(hp.residual.program.steps) == len(
        sp.program.steps
    )
    assert hp.residual.program.num_inputs == len(hp.residual_sources)
    # cached sources reference live prelude slots; leaves reference
    # original input slots
    for kind, ref in hp.residual_sources:
        if kind == "cached":
            assert 0 <= ref < hp.prelude_num_slots
        else:
            assert 0 <= ref < sp.program.num_inputs
    # sliced leaves keep their slice-indexing info in the residual
    assert any(info for info in hp.residual.slot_slices)
    # result metadata is preserved (executors reshape host-side)
    assert hp.residual.program.result_shape == sp.program.result_shape
    assert (
        hp.residual.program.stored_result_shape
        == sp.program.stored_result_shape
    )


def test_noop_when_every_input_touches_a_sliced_leg():
    rng = np.random.default_rng(1)
    ts = [_leaf(rng, [0, 1]), _leaf(rng, [1, 2]), _leaf(rng, [2, 0])]
    tn = CompositeTensor([t.copy() for t in ts])
    path = ContractionPath.simple([(0, 1), (0, 2)])
    # every leaf contains leg 0, 1 or 2 — slicing all three marks every
    # input, so nothing is hoistable
    sp = build_sliced_program(tn, path, Slicing((0, 1, 2), (4, 4, 4)))
    hp = hoist_sliced_program(sp)
    assert hp.is_noop
    assert hp.residual is sp
    arrays = [t.data.into_data() for t in ts]
    naive = execute_sliced_numpy(sp, arrays)
    hoisted = execute_sliced_numpy(sp, arrays, hoist=True)
    np.testing.assert_array_equal(naive, hoisted)


def test_noop_without_slicing():
    ts, tn, path = _ring(2)
    sp = build_sliced_program(tn, path, Slicing((), ()))
    assert hoist_sliced_program(sp).is_noop


def test_numpy_oracle_parity():
    sp, arrays = _sliced(3)
    naive = execute_sliced_numpy(sp, arrays)
    hoisted = execute_sliced_numpy(sp, arrays, hoist=True)
    # identical kernels in identical order: bitwise equality
    np.testing.assert_array_equal(naive, hoisted)
    # reference value
    want = np.einsum("ab,bc,cd,de,ef,fa->", *arrays)
    assert abs(complex(naive.reshape(-1)[0]) - want) <= 1e-10 * abs(want)


def test_numpy_partials_and_parallel_oracle_parity():
    sp, arrays = _sliced(4, legs=(3, 4), dims=(4, 4))
    plain = sliced_partials_numpy(sp, arrays, workers=1)
    hoisted = sliced_partials_numpy(sp, arrays, workers=1, hoist=True)
    np.testing.assert_array_equal(plain, hoisted)
    total = execute_sliced_numpy_parallel(
        sp, arrays, workers=1, hoist=True
    )
    np.testing.assert_allclose(
        total, execute_sliced_numpy(sp, arrays), rtol=1e-12, atol=1e-12
    )


def test_run_prelude_passthrough_on_noop():
    sp, arrays = _sliced(5)
    hp = hoist_sliced_program(sp)
    res = run_prelude(np, hp, [np.asarray(a) for a in arrays])
    assert len(res) == hp.residual.program.num_inputs


@pytest.mark.parametrize("split", [False, True])
def test_chunked_parity(split):
    from tnc_tpu.ops.chunked import execute_sliced_batched_jax

    sp, arrays = _sliced(8, legs=(3, 4), dims=(4, 4))
    naive = execute_sliced_numpy(sp, arrays)
    got = execute_sliced_batched_jax(
        sp,
        arrays,
        batch=4,
        chunk_steps=2,
        split_complex=split,
        dtype="complex128",
        hoist=True,
    )
    np.testing.assert_allclose(got, naive, rtol=1e-10, atol=1e-10)


# one device: the whole slice loop in one program, hoisted and not
@pytest.mark.parametrize("n_devices,hoist", [(2, True), (1, True), (1, False)])
@pytest.mark.parametrize("split", [False, True])
def test_spmd_parity_on_virtual_devices(split, n_devices, hoist):
    from tnc_tpu.parallel.sliced_parallel import (
        distributed_sliced_contraction,
    )

    ts, tn, path = _ring(9)
    slicing = Slicing((3, 4), (4, 4))
    sp = build_sliced_program(tn, path, slicing)
    arrays = [t.data.into_data() for t in ts]
    naive = execute_sliced_numpy(sp, arrays)
    out = distributed_sliced_contraction(
        tn,
        path,
        slicing,
        n_devices=n_devices,
        dtype="complex128",
        split_complex=split,
        hoist=hoist,
    )
    got = out.data.into_data().reshape(sp.program.result_shape)
    np.testing.assert_allclose(got, naive, rtol=1e-10, atol=1e-10)


def test_jax_backend_default_hoist_parity():
    from tnc_tpu.ops.backends import JaxBackend, NumpyBackend

    sp, arrays = _sliced(10, legs=(3,), dims=(4,))
    want = NumpyBackend().execute_sliced(sp, arrays)
    backend = JaxBackend(
        dtype="complex128", split_complex=False
    )
    assert backend.hoist
    got = np.asarray(backend.execute_sliced(sp, arrays))
    np.testing.assert_allclose(got, want, rtol=1e-10, atol=1e-10)
    # per-call override runs the naive loop and must agree too
    got_naive = np.asarray(backend.execute_sliced(sp, arrays, hoist=False))
    np.testing.assert_allclose(got_naive, want, rtol=1e-10, atol=1e-10)


def test_partitioned_local_phase_hoist_parity():
    """Locally sliced partitions (HBM budget) run hoisted when asked and
    still match the single-process oracle."""
    import random

    from tests._cluster_fixture import cluster_chain
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.contractionpath.repartitioning import compute_solution
    from tnc_tpu.parallel.partitioned import (
        distributed_partitioned_contraction,
    )
    from tnc_tpu.tensornetwork.contraction import contract_tensor_network
    from tnc_tpu.tensornetwork.partitioning import find_partitioning

    tn = cluster_chain(k=4, m=7, bond=2, seed=0)
    parts = find_partitioning(tn, 4)
    ptn, ppath, _, _ = compute_solution(tn, parts, rng=random.Random(7))
    flat = Greedy(OptMethod.GREEDY).find_path(tn).replace_path()
    want = complex(
        np.asarray(
            contract_tensor_network(tn, flat, backend="numpy")
            .data.into_data()
        ).reshape(-1)[0]
    )
    got_t = distributed_partitioned_contraction(
        ptn, ppath, dtype="complex128", hbm_bytes=1 << 18, hoist=True
    )
    got = complex(np.asarray(got_t.data.into_data()).reshape(-1)[0])
    assert got == pytest.approx(want, rel=1e-10, abs=1e-12)


def test_flop_accounting_consistency():
    ts, _, path = _ring(11)
    slicing = Slicing((3,), (4,))
    inv, res, hoisted_total = hoisted_sliced_flops(
        ts, path.toplevel, slicing
    )
    naive_total = sliced_flops(ts, path.toplevel, slicing)
    per_slice = naive_total / slicing.num_slices
    assert inv > 0
    assert res <= per_slice * (1 + 1e-9)
    assert abs((inv + res) - per_slice) <= 1e-6 * per_slice
    assert hoisted_total <= naive_total
    assert hoisted_total == pytest.approx(inv + slicing.num_slices * res)
    # the compiled-program split (hoist pass over the SlicedProgram) and
    # the planner's metadata split (StemAccountant over the leg replay)
    # are independent implementations counting the same k*m*n per step —
    # they must agree exactly (bench.py's TPU-free regression guard)
    sp, _ = _sliced(11)
    step_inv, step_res = hoist_step_flops(sp)
    assert step_inv == pytest.approx(inv, rel=1e-9)
    assert step_inv + step_res == pytest.approx(inv + res, rel=1e-9)


def test_stem_accountant_edge_cases():
    ts, _, path = _ring(12)
    acct = StemAccountant(ts, path.toplevel)
    # no removed legs: everything is invariant
    assert acct.invariant_flops(set()) == pytest.approx(acct.total_flops)
    # removing every leg marks every step variant
    all_legs = {leg for t in ts for leg in t.legs}
    assert acct.invariant_flops(all_legs) == 0.0
    # unknown legs are ignored
    assert acct.invariant_flops({9999}) == pytest.approx(acct.total_flops)


def test_hoist_reduces_oracle_work():
    """The acceptance-criterion check on the CPU oracle: hoisted
    execution performs measurably fewer flops; verify via the per-slice
    step counts of the compiled split."""
    sp, arrays = _sliced(13, legs=(4,), dims=(4,))
    hp = hoist_sliced_program(sp)
    num = sp.slicing.num_slices
    naive_steps = num * len(sp.program.steps)
    hoisted_steps = len(hp.prelude_steps) + num * len(
        hp.residual.program.steps
    )
    assert hoisted_steps < naive_steps
    # and the result is still right
    naive = execute_sliced_numpy(sp, arrays)
    hoisted = execute_sliced_numpy(sp, arrays, hoist=True)
    np.testing.assert_array_equal(naive, hoisted)
