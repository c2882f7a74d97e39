"""Strassen stem GEMMs + the kernel promotion ladder.

Pins: one-level Strassen (kl layout) against the plain matmul, the
gauss+strassen complex composition against the complex128 numpy oracle
at the documented tolerance rungs (f32: 2e-5 relative, f64: 1e-12
relative — see ops/strassen.py), eligibility boundaries, the
``KernelPolicy`` planner's forced and cost-model-driven decisions, and
whole-program parity with the strassen rung engaged.
"""

import numpy as np
import pytest

from tnc_tpu.ops import strassen as strassen_mod
from tnc_tpu.ops.strassen import (
    GAUSS_STRASSEN_FLOP_FACTOR,
    STRASSEN_MIN_DIM,
    gauss_strassen_dot_kl,
    strassen_dot_kl,
    strassen_eligible,
)


# -- kernel-level parity ------------------------------------------------


def test_strassen_matches_matmul_f64():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((64, 48))
    b = rng.standard_normal((64, 32))
    got = strassen_dot_kl(np, a, b)
    want = a.T @ b
    denom = float(np.max(np.abs(want)))
    assert float(np.max(np.abs(got - want))) / denom < 1e-12


def test_gauss_strassen_f64_rung():
    """Documented f64 tolerance rung: 1e-12 relative."""
    rng = np.random.default_rng(1)
    ar, ai = rng.standard_normal((64, 48)), rng.standard_normal((64, 48))
    br, bi = rng.standard_normal((64, 32)), rng.standard_normal((64, 32))
    re, im = gauss_strassen_dot_kl(np, ar, ai, br, bi)
    want = (ar + 1j * ai).T @ (br + 1j * bi)
    denom = float(np.max(np.abs(want)))
    assert float(np.max(np.abs((re + 1j * im) - want))) / denom < 1e-12


def test_gauss_strassen_f32_rung():
    """Documented f32 tolerance rung: 2e-5 relative vs the complex128
    oracle — Strassen's pre-product block sums mix magnitudes on top of
    the Gauss mixing, so the pin is looser than the naive 4-dot's."""
    rng = np.random.default_rng(2)
    shape_a, shape_b = (256, 128), (256, 64)
    ar = rng.standard_normal(shape_a).astype(np.float32)
    ai = rng.standard_normal(shape_a).astype(np.float32)
    br = rng.standard_normal(shape_b).astype(np.float32)
    bi = rng.standard_normal(shape_b).astype(np.float32)
    re, im = gauss_strassen_dot_kl(np, ar, ai, br, bi)
    want = (ar + 1j * ai).astype(np.complex128).T @ (
        br + 1j * bi
    ).astype(np.complex128)
    denom = float(np.max(np.abs(want)))
    assert float(np.max(np.abs((re + 1j * im) - want))) / denom < 2e-5


def test_strassen_jax_path_matches():
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    a = rng.standard_normal((32, 16)).astype(np.float32)
    b = rng.standard_normal((32, 24)).astype(np.float32)
    got = np.asarray(strassen_dot_kl(jnp, jnp.asarray(a), jnp.asarray(b)))
    want = a.T @ b
    denom = float(np.max(np.abs(want)))
    assert float(np.max(np.abs(got - want))) / denom < 1e-5


def test_strassen_rejects_odd_dims():
    rng = np.random.default_rng(4)
    with pytest.raises(ValueError):
        strassen_dot_kl(
            np, rng.standard_normal((7, 4)), rng.standard_normal((7, 4))
        )


# -- eligibility --------------------------------------------------------


def test_eligibility_crossover_floor():
    d = STRASSEN_MIN_DIM
    assert strassen_eligible(d, d, d)
    assert not strassen_eligible(d, d // 2, d)  # K below the floor
    assert not strassen_eligible(d - 2, d, d)
    assert strassen_eligible(2 * d, d, d)  # aspect 2 is fine


def test_eligibility_aspect_guard():
    d = STRASSEN_MIN_DIM
    assert not strassen_eligible(8 * d, d, d)  # panel GEMM
    assert strassen_eligible(4 * d, d, d)  # boundary aspect


def test_eligibility_odd_dims():
    d = STRASSEN_MIN_DIM
    assert not strassen_eligible(d + 1, d, d)


def test_flop_factor_is_21_over_32():
    assert abs(GAUSS_STRASSEN_FLOP_FACTOR - 21.0 / 32.0) < 1e-15


# -- the promotion ladder (KernelPolicy) --------------------------------


def _program(qubits=10, depth=5, seed=11):
    from tnc_tpu.builders.connectivity import ConnectivityLayout
    from tnc_tpu.builders.random_circuit import random_circuit
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.ops.program import build_program, flat_leaf_tensors

    rng = np.random.default_rng(seed)
    tn = random_circuit(
        qubits, depth, 0.4, 0.4, rng, ConnectivityLayout.LINE,
        bitstring="*" * qubits,
    )
    result = Greedy(OptMethod.GREEDY).find_path(tn)
    program = build_program(tn, result.replace_path())
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]
    return program, arrays


def test_forced_modes_are_uniform(monkeypatch):
    from tnc_tpu.ops.split_complex import plan_kernels

    program, _ = _program()
    for mode in ("naive", "gauss", "fused"):
        policy = plan_kernels(program, force=mode)
        assert set(policy.modes) == {mode}
        assert policy.chains == ()


def test_env_override_forces(monkeypatch):
    from tnc_tpu.ops.split_complex import default_step_mode, plan_kernels

    program, _ = _program()
    monkeypatch.setenv("TNC_TPU_COMPLEX_MULT", "naive")
    assert set(plan_kernels(program).modes) == {"naive"}
    monkeypatch.setenv("TNC_TPU_COMPLEX_MULT", "auto")
    policy = plan_kernels(program)
    # the ladder's base: each step as its shape decides
    assert policy.modes == tuple(
        default_step_mode(st) for st in program.steps
    )


def _stem_program(shared=8, free=7, seed=3, scale=32.0):
    """One big square-ish contraction: k = 2^shared, m = n = 2^free —
    the stem-GEMM shape the hoist pass isolates."""
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.ops.program import build_program, flat_leaf_tensors
    from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor
    from tnc_tpu.tensornetwork.tensordata import TensorData

    rng = np.random.default_rng(seed)
    shared_legs = list(range(shared))
    a_free = list(range(shared, shared + free))
    b_free = list(range(shared + free, shared + 2 * free))

    def leaf(legs):
        shape = [2] * len(legs)
        data = rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
        return LeafTensor(legs, [2] * len(legs), TensorData.matrix(data / scale))

    tn = CompositeTensor([leaf(shared_legs + a_free), leaf(shared_legs + b_free)])
    program = build_program(tn, ContractionPath.simple([(0, 1)]))
    arrays = [l.data.into_data() for l in flat_leaf_tensors(tn)]
    return program, arrays


def test_auto_policy_promotes_stem_to_strassen(monkeypatch):
    """With the crossover lowered into test range, the auto ladder
    promotes the big square-ish stem step and leaves small-step
    programs on gauss."""
    from tnc_tpu.ops.program import step_dims
    from tnc_tpu.ops.split_complex import plan_kernels

    monkeypatch.setattr(strassen_mod, "STRASSEN_MIN_DIM", 8)
    program, _ = _stem_program()
    policy = plan_kernels(program)
    assert policy.modes == ("strassen",)
    m, k, n = step_dims(program.steps[0])
    assert strassen_eligible(m, k, n)

    small_program, _ = _program(qubits=12, depth=6)
    small_policy = plan_kernels(small_program)
    assert "strassen" not in small_policy.modes  # nothing clears 8^3


def test_auto_policy_never_plans_chains():
    """The chain kernel has never compiled for a TPU, so the unforced
    ladder plans no chain — with no model, and with a fitted model at
    any dispatch overhead; ``force="chain"`` is the only way to one."""
    from tnc_tpu.obs.calibrate import CalibratedCostModel
    from tnc_tpu.ops.split_complex import plan_kernels

    program, _ = _program()
    assert plan_kernels(program).chains == ()
    free_dispatch = CalibratedCostModel(flops_per_s=1e12, dispatch_s=0.0)
    assert plan_kernels(program, cost_model=free_dispatch).chains == ()
    costly = CalibratedCostModel(flops_per_s=1e12, dispatch_s=1e-3)
    assert plan_kernels(program, cost_model=costly).chains == ()
    assert plan_kernels(program, force="chain").chains != ()


def test_chained_steps_carry_naive_mode():
    from tnc_tpu.ops.split_complex import plan_kernels

    program, _ = _program()
    policy = plan_kernels(program, force="chain")
    assert policy.chains
    for i in policy.chained_steps():
        assert policy.modes[i] == "naive"
    assert policy.dispatch_count() < len(program.steps)


def test_policy_is_part_of_jit_key():
    from tnc_tpu.ops.split_complex import KernelPolicy

    a = KernelPolicy(("gauss", "gauss"))
    b = KernelPolicy(("gauss", "naive"))
    assert a.signature() != b.signature()


def test_kernel_plan_summary_buckets():
    from tnc_tpu.ops.split_complex import (
        kernel_plan_summary,
        plan_kernels,
    )

    program, _ = _program()
    policy = plan_kernels(program, force="chain")
    summary = kernel_plan_summary(program, policy)
    assert summary["dispatches"] == policy.dispatch_count()
    assert summary["chains"] == len(policy.chains)
    total_steps = sum(b["steps"] for b in summary["buckets"].values())
    assert total_steps == len(program.steps)
    for b in summary["buckets"].values():
        assert b["effective_flops"] <= b["flops"] + 1e-9


# -- whole-program parity with the strassen rung engaged ----------------


def test_step_strassen_matches_oracle(monkeypatch):
    """apply_step_split(mode='strassen') vs the complex128 oracle on a
    real program's steps (crossover lowered so small steps qualify)."""
    from tnc_tpu.ops.backends import NumpyBackend, place_buffers
    from tnc_tpu.ops.split_complex import (
        combine_array,
        plan_kernels,
        run_steps_split,
    )

    monkeypatch.setattr(strassen_mod, "STRASSEN_MIN_DIM", 8)
    program, arrays = _stem_program(seed=7)
    policy = plan_kernels(program, force="strassen")
    assert "strassen" in policy.modes

    import jax.numpy as jnp

    buffers = place_buffers(arrays, "complex64", True)
    out = run_steps_split(jnp, program, buffers, "float32", policy=policy)
    got = combine_array(*out).reshape(program.result_shape)
    want = NumpyBackend(dtype=np.complex128).execute(program, arrays)
    denom = max(float(np.max(np.abs(want))), 1e-30)
    assert float(np.max(np.abs(got - want))) / denom < 2e-5


def test_host_split_strassen_matches_oracle(monkeypatch):
    """The host (numpy) split path under mode='strassen' — the same
    code the oracle-side parity pins run through."""
    from tnc_tpu.ops.backends import NumpyBackend
    from tnc_tpu.ops.split_complex import (
        combine_array,
        plan_kernels,
        run_steps_split,
        split_array,
    )

    monkeypatch.setattr(strassen_mod, "STRASSEN_MIN_DIM", 8)
    program, arrays = _program(qubits=10, depth=4, seed=5)
    policy = plan_kernels(program, force="strassen")
    buffers = [split_array(a, "float64") for a in arrays]
    out = run_steps_split(np, program, buffers, policy=policy)
    got = combine_array(*out).reshape(program.result_shape)
    want = NumpyBackend(dtype=np.complex128).execute(program, arrays)
    denom = max(float(np.max(np.abs(want))), 1e-30)
    assert float(np.max(np.abs(got - want))) / denom < 1e-12


def test_forced_strassen_below_crossover_falls_back_to_gauss():
    """Forcing strassen on a program whose steps are all under the
    crossover must run gauss (never crash on odd/small shapes) and
    hold the gauss parity rung."""
    import os

    from tnc_tpu.ops.backends import JaxBackend, NumpyBackend

    program, arrays = _program(qubits=8, depth=4, seed=9)
    os.environ["TNC_TPU_COMPLEX_MULT"] = "strassen"
    try:
        got = JaxBackend(
            dtype="complex64", split_complex=True, precision="float32"
        ).execute(program, arrays)
    finally:
        del os.environ["TNC_TPU_COMPLEX_MULT"]
    want = NumpyBackend(dtype=np.complex128).execute(program, arrays)
    denom = max(float(np.max(np.abs(want))), 1e-30)
    assert float(np.max(np.abs(got - want))) / denom < 1e-4


def test_prelude_auto_promotion_keeps_parity(monkeypatch):
    """Hoisted split-complex execution with the prelude's auto strassen
    promotion armed (crossover lowered) stays on the oracle."""
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.contractionpath.slicing import Slicing
    from tnc_tpu.ops.backends import JaxBackend, NumpyBackend
    from tnc_tpu.ops.program import build_program
    from tnc_tpu.ops.sliced import build_sliced_program
    from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor
    from tnc_tpu.tensornetwork.tensordata import TensorData

    monkeypatch.setattr(strassen_mod, "STRASSEN_MIN_DIM", 8)
    rng = np.random.default_rng(0)

    def mk(legs, dims):
        data = rng.standard_normal(dims) + 1j * rng.standard_normal(dims)
        return LeafTensor(legs, dims, TensorData.matrix(data / 8.0))

    # (0,3) is slice-invariant (legs 4,5,6 untouched): a 16^3 stem GEMM
    tn = CompositeTensor(
        [
            mk([4, 5], [16, 16]),
            mk([0, 1], [4, 4]),
            mk([1, 2], [4, 4]),
            mk([5, 6, 0], [16, 16, 4]),
            mk([6, 2, 4], [16, 4, 16]),
        ]
    )
    path = ContractionPath.simple([(0, 3), (1, 2), (0, 4), (0, 1)])
    sp = build_sliced_program(tn, path, Slicing((0,), (4,)))
    arrays = [t.data.into_data() for t in tn.tensors]

    want = NumpyBackend(dtype=np.complex128).execute_sliced(sp, arrays)
    got = JaxBackend(
        dtype="complex64", split_complex=True, precision="float32"
    ).execute_sliced(sp, arrays)
    denom = max(float(np.max(np.abs(want))), 1e-30)
    assert float(np.max(np.abs(np.asarray(got) - want))) / denom < 1e-4
