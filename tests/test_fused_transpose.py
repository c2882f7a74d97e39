"""Fused transpose-matmul kernel rung + the dot-precision ladder.

The ``fused_transpose`` mode streams each operand's macro-dim
permutation through the Pallas BlockSpec index maps instead of
materializing it through HBM (docs/future_work.md item 2); the
``precision_modes`` rungs run chosen steps' dots at bf16x3. These tests
pin: interpret-mode BITWISE parity of the kernel against its
shared-body reference on randomized eligible layouts, the eligibility
boundary (non-tile-multiple perms, k=1, staged prep, batch-carrying
buffers), end-to-end executor parity under the forced mode AND the full
auto ladder vs the complex128 oracle, cost-model-driven promotion, the
policy-signature cache-key contract for precision rungs, calibrated
chain-bucket expansion, and the transpose-pass bytes accounting
(``steps_bytes``) with its perf-gate invariant.
"""

import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tnc_tpu.ops.pallas_complex import (
    MIN_FLOPS,
    fused_transpose_dot_kl,
    fused_transpose_reference,
    operand_layout,
    transpose_dot_ineligible_reason,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rand(shape, rng):
    return rng.standard_normal(shape).astype(np.float32)


# -- layout derivation --------------------------------------------------


def test_operand_layout_identity_kl():
    lay = operand_layout((256, 512), None, (256, 512), True)
    assert lay.k_axes == (0,) and lay.f_axes == (1,)
    assert (lay.kd, lay.fd) == (0, 1)
    assert (lay.k_size, lay.f_size) == (256, 512)


def test_operand_layout_identity_lk():
    lay = operand_layout((512, 256), None, (512, 256), False)
    assert lay.k_axes == (1,) and lay.f_axes == (0,)


def test_operand_layout_rank3_transpose():
    # stored (x=4, m=512, y=64), permuted (x, y, m): k = x*y = 256
    lay = operand_layout((4, 512, 64), (0, 2, 1), (256, 512), True)
    assert lay.k_axes == (0, 2) and lay.f_axes == (1,)
    assert lay.kd == 2 and lay.fd == 1
    assert lay.k_size == 256 and lay.f_size == 512


def test_operand_layout_degenerate_k_is_none():
    assert operand_layout((4, 8), None, (1, 32), True) is None  # k = 1
    # k not a clean prefix product of permuted dims
    assert operand_layout((4, 8), None, (2, 16), True) is None


# -- eligibility boundary -----------------------------------------------


def test_ineligible_k1_and_flop_floor():
    a = operand_layout((1, 4096), None, (1, 4096), True)
    assert a is None  # k = 1 degenerates at layout derivation
    big = operand_layout((256, 512), None, (256, 512), True)
    assert (
        transpose_dot_ineligible_reason(None, big, 1, 4096, 4096)
        == "layout"
    )
    small = operand_layout((16, 16), None, (16, 16), True)
    assert (
        transpose_dot_ineligible_reason(small, small, 16, 16, 16)
        == "flop_floor"
    )


def test_ineligible_non_minor_active_axes():
    # permuted (y, m, x): fastest free digit lands on stored axis 0 —
    # tiles would slide along a leading (badly-tiled) axis
    lay = operand_layout((128, 256, 8), (2, 0, 1), (8, 128, 256), False)
    other = operand_layout((256, 512), None, (256, 512), True)
    assert lay is not None
    assert (
        transpose_dot_ineligible_reason(other, lay, 256, 512, 1024)
        == "minor_axes"
    )


def test_ineligible_non_tile_multiple_dims():
    # N = 96 < 128 lane floor and 96 has no pow2 tile >= 128
    a = operand_layout((512, 512), None, (512, 512), True)
    b = operand_layout((512, 96), None, (512, 96), True)
    assert (
        transpose_dot_ineligible_reason(a, b, 512, 512, 96) == "tile_floor"
    )
    # exactly at the flop floor: eligible
    k = m = n = 128
    sq = operand_layout((128, 128), None, (128, 128), True)
    assert 2 * k * m * n == MIN_FLOPS
    assert transpose_dot_ineligible_reason(sq, sq, k, m, n) is None


def test_step_eligibility_staged_and_batch(monkeypatch):
    """Steps carrying a staged prep plan skip with reason
    ``staged_prep``; buffers carrying a leading batch axis skip with
    reason ``batch`` (counted, never an exception)."""
    from tnc_tpu import obs
    from tnc_tpu.ops.split_complex import (
        _step_lowering,
        fused_transpose_ineligible_reason,
    )

    program, _ = _eligible_program()
    step = program.steps[0]
    staged = step.__class__(**{
        **{f: getattr(step, f) for f in step.__dataclass_fields__},
        "a_ops": (("reshape", (4, 512, 64)),),
    })
    assert fused_transpose_ineligible_reason(staged) == "staged_prep"

    obs.configure(enabled=True, registry=obs.MetricsRegistry())
    try:
        rng = np.random.default_rng(0)
        # leading batch axis: sizes no longer match the stored views
        bshape = (3,) + tuple(step.a_view)
        apair = (
            jnp.asarray(_rand(bshape, rng)), jnp.asarray(_rand(bshape, rng))
        )
        bpair = (
            jnp.asarray(_rand(step.b_view, rng)),
            jnp.asarray(_rand(step.b_view, rng)),
        )
        # routed away: the prep + naive dots, decided before any op
        assert _step_lowering(apair, bpair, step, "fused_transpose") == (
            "naive", "matrix",
        )
        counters = obs.get_registry().snapshot()["counters"]
    finally:
        obs.configure(enabled=False)
    assert any(
        k.startswith("ops.fused_transpose_fallback") and "reason=batch" in k
        for k in counters
    ), counters


# -- randomized bitwise parity vs the shared-body reference -------------


@pytest.mark.parametrize("seed", range(4))
def test_kernel_bitwise_equals_reference_randomized(seed):
    """Randomized eligible layouts (identity kl/lk, rank-3 macro
    transposes on either side): the Pallas kernel in interpret mode is
    BIT-identical to the shared-body reference — fusion changed
    streaming structure only."""
    rng = np.random.default_rng(100 + seed)

    def pick_layout():
        kind = rng.integers(0, 3)
        if kind == 0:  # identity (K, F)
            k, f = 256, int(rng.choice([256, 384, 512]))
            return (k, f), operand_layout((k, f), None, (k, f), True)
        if kind == 1:  # identity (F, K)
            k, f = 256, int(rng.choice([256, 512]))
            return (f, k), operand_layout((f, k), None, (f, k), False)
        # rank-3 with macro transpose: stored (x, f, y), k = x*y = 256
        x, y = 4, 64
        f = int(rng.choice([256, 512]))
        view = (x, f, y)
        return view, operand_layout(view, (0, 2, 1), (256, f), True)

    a_shape, a_lay = pick_layout()
    b_shape, b_lay = pick_layout()
    m, n = a_lay.f_size, b_lay.f_size
    assert transpose_dot_ineligible_reason(a_lay, b_lay, 256, m, n) is None
    ar, ai = _rand(a_shape, rng), _rand(a_shape, rng)
    br, bi = _rand(b_shape, rng), _rand(b_shape, rng)
    got_r, got_i = jax.jit(
        lambda a, b, c, d: fused_transpose_dot_kl(
            a, b, c, d, a_lay, b_lay, interpret=True
        )
    )(ar, ai, br, bi)
    want_r, want_i = fused_transpose_reference(ar, ai, br, bi, a_lay, b_lay)
    assert got_r.shape == (m, n)
    assert np.array_equal(np.asarray(got_r), np.asarray(want_r))
    assert np.array_equal(np.asarray(got_i), np.asarray(want_i))


def test_kernel_matches_complex128_oracle():
    """Numeric (not just structural) correctness: permuted operand dot
    against the complex128 einsum."""
    rng = np.random.default_rng(5)
    a_lay = operand_layout((4, 512, 64), (0, 2, 1), (256, 512), True)
    b_lay = operand_layout((256, 384), None, (256, 384), True)
    ar, ai = _rand((4, 512, 64), rng), _rand((4, 512, 64), rng)
    br, bi = _rand((256, 384), rng), _rand((256, 384), rng)
    re, im = fused_transpose_dot_kl(
        ar, ai, br, bi, a_lay, b_lay, interpret=True
    )
    a128 = (ar + 1j * ai).astype(np.complex128).transpose(0, 2, 1)
    a2 = a128.reshape(256, 512)
    want = a2.T @ (br + 1j * bi).astype(np.complex128)
    got = np.asarray(re) + 1j * np.asarray(im)
    denom = float(np.max(np.abs(want)))
    assert float(np.max(np.abs(got - want))) / denom < 1e-5


# -- end-to-end through the executors -----------------------------------


def _eligible_program(seed=3):
    """A contraction whose first operand needs a rank-3 macro
    transpose and clears every fused-transpose gate."""
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.ops.program import build_program, flat_leaf_tensors
    from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor
    from tnc_tpu.tensornetwork.tensordata import TensorData

    rng = np.random.default_rng(seed)

    def leaf(legs, dims):
        data = (
            rng.standard_normal(dims) + 1j * rng.standard_normal(dims)
        ) / 8.0
        return LeafTensor(legs, dims, TensorData.matrix(data))

    tn = CompositeTensor(
        [leaf([0, 1, 2], [4, 512, 64]), leaf([0, 2, 3], [4, 64, 384])]
    )
    program = build_program(tn, ContractionPath.simple([(0, 1)]))
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]
    return program, arrays


def test_forced_mode_engages_and_matches_oracle(monkeypatch):
    """TNC_TPU_COMPLEX_MULT=fused_transpose: the eligible step routes
    through the kernel (counted by a spy), the program matches the
    complex128 oracle, and no fallback fires."""
    from tnc_tpu import obs
    from tnc_tpu.ops import pallas_complex
    from tnc_tpu.ops.backends import JaxBackend, NumpyBackend

    monkeypatch.setenv("TNC_TPU_COMPLEX_MULT", "fused_transpose")
    program, arrays = _eligible_program()
    calls = []
    real = pallas_complex.fused_transpose_dot_kl

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(pallas_complex, "fused_transpose_dot_kl", counting)
    obs.configure(enabled=True, registry=obs.MetricsRegistry())
    try:
        want = NumpyBackend(dtype=np.complex128).execute(program, arrays)
        got = JaxBackend(
            dtype="complex64", split_complex=True, precision="float32"
        ).execute(program, arrays)
        counters = obs.get_registry().snapshot()["counters"]
    finally:
        obs.configure(enabled=False)
    assert calls, "fused transpose kernel was never invoked"
    assert not any(
        k.startswith("ops.fused_transpose_fallback") for k in counters
    ), counters
    denom = max(float(np.max(np.abs(want))), 1e-30)
    assert float(np.max(np.abs(got - want))) / denom < 1e-5


def test_forced_mode_falls_back_counted_on_ineligible(monkeypatch):
    """A whole random circuit under the forced mode: ineligible steps
    fall back to prep+naive (counted with reasons), output parity
    holds — the counted-fallback contract."""
    from tnc_tpu import obs
    from tnc_tpu.builders.connectivity import ConnectivityLayout
    from tnc_tpu.builders.random_circuit import random_circuit
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.ops.backends import JaxBackend, NumpyBackend
    from tnc_tpu.ops.program import build_program, flat_leaf_tensors

    monkeypatch.setenv("TNC_TPU_COMPLEX_MULT", "fused_transpose")
    rng = np.random.default_rng(9)
    tn = random_circuit(
        10, 5, 0.4, 0.4, rng, ConnectivityLayout.LINE, bitstring="*" * 10
    )
    program = build_program(
        tn, Greedy(OptMethod.GREEDY).find_path(tn).replace_path()
    )
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]
    obs.configure(enabled=True, registry=obs.MetricsRegistry())
    try:
        want = NumpyBackend(dtype=np.complex128).execute(program, arrays)
        got = JaxBackend(
            dtype="complex64", split_complex=True, precision="float32"
        ).execute(program, arrays)
        counters = obs.get_registry().snapshot()["counters"]
    finally:
        obs.configure(enabled=False)
    reasons = {
        k for k in counters if k.startswith("ops.fused_transpose_fallback{")
    }
    assert reasons, "tiny-step circuit produced no counted fallbacks"
    denom = max(float(np.max(np.abs(want))), 1e-30)
    assert float(np.max(np.abs(got - want))) / denom < 1e-5


def test_auto_ladder_end_to_end_matches_oracle(monkeypatch):
    """The FULL auto ladder (fused-transpose + strassen + precision
    rungs, planned from an injected calibrated model with a
    bandwidth term) through the jitted executor, allclose-pinned
    against the complex128 numpy oracle."""
    from tnc_tpu.obs.calibrate import CalibratedCostModel
    from tnc_tpu.ops.backends import jit_program, place_buffers, NumpyBackend
    from tnc_tpu.ops.split_complex import combine_array, plan_kernels

    monkeypatch.delenv("TNC_TPU_COMPLEX_MULT", raising=False)
    monkeypatch.delenv("TNC_TPU_DOT_PRECISION", raising=False)
    program, arrays = _eligible_program()
    model = CalibratedCostModel(
        flops_per_s=1e12, dispatch_s=2e-5, bytes_per_s=1e9
    )
    policy = plan_kernels(program, cost_model=model)
    assert "fused_transpose" in policy.modes, policy.modes
    fn = jit_program(
        program, True, "float32", donate=False, policy=policy,
        interpret=True,
    )
    out = fn(place_buffers(arrays, "complex64", True))
    got = np.asarray(combine_array(*out)).reshape(program.result_shape)
    want = NumpyBackend(dtype=np.complex128).execute(program, arrays)
    denom = max(float(np.max(np.abs(want))), 1e-30)
    assert float(np.max(np.abs(got - want))) / denom < 1e-5


# -- cost-model-driven promotion ----------------------------------------


def test_auto_promotion_requires_bandwidth_evidence():
    """The fused-transpose rung promotes only under a fitted bandwidth
    term: no model / no bytes term → gauss; a bandwidth-bound model →
    fused_transpose on the transpose-carrying eligible step."""
    from tnc_tpu.obs.calibrate import CalibratedCostModel
    from tnc_tpu.ops.split_complex import plan_kernels

    program, _ = _eligible_program()
    assert plan_kernels(program).modes == ("gauss",)
    flops_only = CalibratedCostModel(flops_per_s=1e12, dispatch_s=1e-5)
    assert plan_kernels(program, cost_model=flops_only).modes == ("gauss",)
    bandwidth_bound = CalibratedCostModel(
        flops_per_s=1e13, dispatch_s=1e-5, bytes_per_s=1e9
    )
    assert plan_kernels(program, cost_model=bandwidth_bound).modes == (
        "fused_transpose",
    )
    # a model where recomputing flops is nearly free but bandwidth is
    # effectively infinite: the saved pass is worthless → gauss
    fast_bytes = CalibratedCostModel(
        flops_per_s=1e6, dispatch_s=1e-5, bytes_per_s=1e30
    )
    assert plan_kernels(program, cost_model=fast_bytes).modes == ("gauss",)


def test_chain_ceiling_admits_medium_steps_only_when_forced():
    """``chain_groups`` admits a VMEM-small medium-step run under a
    raised ``max_flops``; ``plan_kernels`` reaches it only through
    ``force="chain"`` — the unforced ladder plans no chain whatever a
    fitted model says about dispatch cost (the chain kernel has never
    compiled for a TPU)."""
    from tnc_tpu.obs.calibrate import CalibratedCostModel
    from tnc_tpu.ops.program import chain_groups

    costly = CalibratedCostModel(flops_per_s=1e12, dispatch_s=1e-2)
    ceiling = 2.0 * costly.dispatch_equivalent_flops()
    assert ceiling > MIN_FLOPS

    # a matrix-product chain whose every step is ABOVE the static
    # small-step bound (2*256^3 = 2^25 flops) yet VMEM-small and
    # trivially carried
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.ops.program import build_program, step_flops
    from tnc_tpu.ops.split_complex import plan_kernels
    from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor
    from tnc_tpu.tensornetwork.tensordata import TensorData

    rng = np.random.default_rng(21)

    def mat(legs):
        data = (
            rng.standard_normal((256, 256))
            + 1j * rng.standard_normal((256, 256))
        ) / 16.0
        return LeafTensor(legs, [256, 256], TensorData.matrix(data))

    tn = CompositeTensor([mat([i, i + 1]) for i in range(4)])
    program = build_program(
        tn, ContractionPath.simple([(0, 1), (0, 2), (0, 3)])
    )
    assert all(2.0 * step_flops(st) >= MIN_FLOPS for st in program.steps)
    assert chain_groups(program.steps) == ()  # static bound: too big
    expanded = chain_groups(program.steps, max_flops=ceiling)
    assert expanded, "raised ceiling did not admit the medium-step chain"

    assert plan_kernels(
        program, force="chain", chain_max_flops=ceiling
    ).chains
    assert not plan_kernels(program, cost_model=costly).chains
    assert not plan_kernels(program).chains


# -- precision ladder ---------------------------------------------------


def test_plan_precision_modes_forced_and_budgeted(monkeypatch):
    """TNC_TPU_DOT_PRECISION forces every step; unforced the ladder
    promotes only compute-dominated stem steps under a parity budget
    that clears the documented bf16x3 rung."""
    from tnc_tpu.obs.calibrate import CalibratedCostModel
    from tnc_tpu.ops.split_complex import (
        HIGH_PRECISION_STEP_REL,
        plan_precision_modes,
        step_bucket,
    )
    from tnc_tpu.ops import strassen as strassen_mod

    program, _ = _eligible_program()
    monkeypatch.setenv("TNC_TPU_DOT_PRECISION", "high")
    assert plan_precision_modes(program.steps) == ("high",)
    monkeypatch.delenv("TNC_TPU_DOT_PRECISION")
    # unforced, no model: no rungs
    assert plan_precision_modes(program.steps) == ()

    # lower the strassen crossover so the fixture step is stem-bucket
    monkeypatch.setattr(strassen_mod, "STRASSEN_MIN_DIM", 8)
    assert step_bucket(program.steps[0]) == "stem"
    compute_bound = CalibratedCostModel(
        flops_per_s=1e9, dispatch_s=1e-5, bytes_per_s=1e30
    )
    assert plan_precision_modes(
        program.steps, cost_model=compute_bound
    ) == ("high",)
    # a parity budget tighter than the rung never promotes
    assert plan_precision_modes(
        program.steps, cost_model=compute_bound,
        parity_budget=HIGH_PRECISION_STEP_REL,
    ) == ()
    # bandwidth-bound stem: dots aren't the bottleneck — no promotion
    bw_bound = CalibratedCostModel(
        flops_per_s=1e30, dispatch_s=1e-5, bytes_per_s=1e6
    )
    assert plan_precision_modes(program.steps, cost_model=bw_bound) == ()


def test_dot_precision_env_rejects_typos(monkeypatch):
    """A typo'd A/B knob must fail loudly, not silently measure the
    highest rung under a mislabeled name."""
    from tnc_tpu.ops.split_complex import dot_precision_forced

    monkeypatch.setenv("TNC_TPU_DOT_PRECISION", "hi")
    with pytest.raises(ValueError, match="TNC_TPU_DOT_PRECISION"):
        dot_precision_forced()
    monkeypatch.setenv("TNC_TPU_DOT_PRECISION", "high")
    assert dot_precision_forced() == "high"
    monkeypatch.setenv("TNC_TPU_DOT_PRECISION", "auto")
    assert dot_precision_forced() is None


def test_auto_precision_never_stacks_on_strassen(monkeypatch):
    """The auto bf16x3 rung must not ride a Strassen step (the budget
    models the plain-dot rung only); a forced env stays global."""
    from tnc_tpu.obs.calibrate import CalibratedCostModel
    from tnc_tpu.ops import strassen as strassen_mod
    from tnc_tpu.ops.split_complex import plan_kernels

    monkeypatch.setattr(strassen_mod, "STRASSEN_MIN_DIM", 8)
    program, _ = _eligible_program()
    compute_bound = CalibratedCostModel(
        flops_per_s=1e9, dispatch_s=1e-5, bytes_per_s=1e30
    )
    policy = plan_kernels(program, cost_model=compute_bound)
    assert policy.modes == ("strassen",)
    assert policy.precision_modes == ()  # stripped, not 'high'
    monkeypatch.setenv("TNC_TPU_DOT_PRECISION", "high")
    forced = plan_kernels(program, cost_model=compute_bound)
    assert forced.precision_modes == ("high",)  # explicit A/B: global


def test_precision_modes_are_part_of_policy_signature():
    """Two policies identical in modes and chains but differing in
    precision rungs must have different signatures — the jit cache key
    contract: a forced-high trace must never be served for an auto
    trace."""
    from tnc_tpu.ops.split_complex import KernelPolicy

    a = KernelPolicy(("gauss", "gauss"))
    b = KernelPolicy(("gauss", "gauss"), (), ("high", "high"))
    c = KernelPolicy(("gauss", "gauss"), (), ("highest", "high"))
    assert a.signature() != b.signature() != c.signature()
    assert a.precision_mode(0) == "" and b.precision_mode(1) == "high"


def test_dot_precision_env_is_a_jit_cache_key(monkeypatch):
    """Flipping TNC_TPU_DOT_PRECISION between calls must re-trace, not
    serve the stale executable (complex_mult_key-style): the jit cache
    records a miss for each env value."""
    from tnc_tpu import obs
    from tnc_tpu.ops.backends import jit_program, place_buffers
    from tnc_tpu.ops.split_complex import combine_array, dot_precision_key

    program, arrays = _eligible_program(seed=17)
    monkeypatch.delenv("TNC_TPU_DOT_PRECISION", raising=False)
    assert dot_precision_key() == "auto"
    obs.configure(enabled=True, registry=obs.MetricsRegistry())
    try:
        fn_auto = jit_program(program, True, "float32", donate=False)
        monkeypatch.setenv("TNC_TPU_DOT_PRECISION", "high")
        assert dot_precision_key() == "high"
        fn_high = jit_program(program, True, "float32", donate=False)
        counters = obs.get_registry().snapshot()["counters"]
    finally:
        obs.configure(enabled=False)
    assert counters.get("jit_cache.miss", 0) >= 2, counters
    assert fn_auto is not fn_high
    # and the forced-high executable still meets a (relaxed) parity
    # target on CPU (precision is a no-op off-TPU, but the trace must
    # run)
    out = fn_high(place_buffers(arrays, "complex64", True))
    got = np.asarray(combine_array(*out)).reshape(program.result_shape)
    assert np.all(np.isfinite(got))


# -- bytes accounting ----------------------------------------------------


def test_steps_bytes_accounts_transpose_pass():
    """steps_bytes prices the materialized macro transpose (read +
    write per permuted operand) on top of the matmul movement; the
    fused_transpose mode's prediction drops exactly that pass."""
    from tnc_tpu.ops.program import (
        step_elems,
        step_prep_elems,
        steps_bytes,
    )

    program, _ = _eligible_program()
    st = program.steps[0]
    assert st.a_perm is not None or st.b_perm is not None
    view_elems = float(np.prod(st.a_view)) + float(np.prod(st.b_view))
    out_elems = float(np.prod(st.out_store))
    prep = step_prep_elems(st)
    assert prep > 0.0
    naive_in, naive_out = step_elems(st)
    assert naive_in == view_elems + prep and naive_out == out_elems
    fused_in, _ = step_elems(st, mode="fused_transpose")
    assert fused_in == view_elems
    assert steps_bytes([st], 1.0) == naive_in + naive_out


def test_r04_style_transpose_step_misprediction_pinned():
    """Regression pin for the r04 roofline misprediction class: a
    transpose-dominated step (operand permuted through HBM) must
    predict MORE traffic than the bare matmul movement — the
    pre-fix ``steps_bytes`` under-predicted exactly these steps, which
    skewed the CalibratedCostModel bytes term. The pinned shape mirrors
    the north-star residual's permuted stem feeds (macro view
    (4, 512, 64), perm (0, 2, 1))."""
    from tnc_tpu.ops.program import step_elems, steps_bytes

    program, _ = _eligible_program()
    st = program.steps[0]
    matmul_only = (
        float(np.prod(st.a_view))
        + float(np.prod(st.b_view))
        + float(np.prod(st.out_store))
    )
    # the old accounting: exactly the matmul movement — now a strict
    # under-count for this step (one full operand read + write short)
    assert steps_bytes([st], 1.0) == pytest.approx(
        matmul_only + 2.0 * float(np.prod(st.a_view))
    )
    assert steps_bytes([st], 1.0) > matmul_only
    # and the fused rung's credited prediction returns to the matmul
    # movement — the saved pass, visible to the roofline
    fused_in, fused_out = step_elems(st, mode="fused_transpose")
    assert fused_in + fused_out == matmul_only


def test_kernel_plan_summary_bytes_and_precision_fields():
    from tnc_tpu.obs.calibrate import CalibratedCostModel
    from tnc_tpu.ops.split_complex import kernel_plan_summary, plan_kernels

    program, _ = _eligible_program()
    model = CalibratedCostModel(
        flops_per_s=1e12, dispatch_s=2e-5, bytes_per_s=1e9
    )
    policy = plan_kernels(program, cost_model=model)
    kplan = kernel_plan_summary(program, policy)
    (bucket,) = kplan["buckets"].values()
    assert bucket["transpose_steps"] == 1
    assert bucket["pred_bytes_planned"] < bucket["pred_bytes_naive"]
    assert bucket["pred_bytes_per_step_planned"] < bucket[
        "pred_bytes_per_step_naive"
    ]
    assert "precision" in bucket and sum(bucket["precision"].values()) == 1
    # unplanned (gauss) policy: planned == naive
    kplan_gauss = kernel_plan_summary(program, plan_kernels(program))
    (bg,) = kplan_gauss["buckets"].values()
    assert bg["pred_bytes_planned"] == bg["pred_bytes_naive"]


def test_perf_gate_fails_injected_bytes_regression(tmp_path):
    """The perf gate's predicted-HBM-bytes invariant: a candidate
    whose transpose-carrying bucket claims MORE planned bytes than
    naive must exit 1 (injected regression), and a healthy record must
    pass."""
    spec = importlib.util.spec_from_file_location(
        "perf_gate", os.path.join(REPO, "scripts", "perf_gate.py")
    )
    gate = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gate)

    def record(planned):
        return {
            "metric": "m", "value": 1.0,
            "kernel_plan": {
                "buckets": {
                    "medium": {
                        "steps": 4,
                        "transpose_steps": 2,
                        "pred_bytes_naive": 1000.0,
                        "pred_bytes_planned": planned,
                        "pred_bytes_per_step_naive": 250.0,
                        "pred_bytes_per_step_planned": planned / 4.0,
                    }
                }
            },
        }

    healthy = record(800.0)
    code, _ = gate.compare(healthy, healthy)
    assert code == 0
    code, msgs = gate.compare(healthy, record(1200.0))
    assert code == 1
    assert any("planned HBM bytes" in m for m in msgs)


def test_perf_gate_bucket_mfu_target_table(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "perf_gate", os.path.join(REPO, "scripts", "perf_gate.py")
    )
    gate = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gate)
    rec = {"metric": "m", "value": 1.0}
    below = dict(rec, kernel_buckets={
        "source": "jax",
        "buckets": {"stem": {"mfu": 0.10, "precision": {"default": 3}}},
    })
    code, msgs = gate.compare(rec, below)
    assert code == 0  # warn-only
    assert any("below the 0.22 target" in m for m in msgs)
    ok = dict(rec, kernel_buckets={
        "source": "jax", "buckets": {"stem": {"mfu": 0.30}}
    })
    code, msgs = gate.compare(rec, ok)
    assert not any("below the" in m for m in msgs)


# -- span accounting under the rung -------------------------------------


def test_run_steps_timed_credits_saved_transpose_and_precision(enabled_obs=None):
    from tnc_tpu import obs
    from tnc_tpu.ops.backends import place_buffers, run_steps_timed
    from tnc_tpu.ops.split_complex import KernelPolicy

    program, arrays = _eligible_program()
    n = len(program.steps)

    def spans(policy):
        obs.configure(enabled=True, registry=obs.MetricsRegistry())
        try:
            buffers = place_buffers(arrays, "complex64", True)
            run_steps_timed(
                jnp, program, buffers, 8.0, split_complex=True,
                precision="float32", sync=jax.block_until_ready,
                policy=policy, interpret=True,
            )
            return [
                r for r in obs.get_registry().span_records()
                if r.name.startswith("step[")
            ]
        finally:
            obs.configure(enabled=False)

    fused = spans(
        KernelPolicy(("fused_transpose",) * n, (), ("high",) * n)
    )
    naive = spans(KernelPolicy(("naive",) * n))
    assert fused[0].args["mode"] == "fused_transpose"
    assert fused[0].args["precision"] == "high"
    assert naive[0].args["precision"] == "default"
    assert fused[0].args["bytes_in"] < naive[0].args["bytes_in"]
