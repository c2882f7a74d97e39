"""Fused split-complex Pallas kernel (interpret mode on CPU).

The ``fused`` complex-mult mode computes re/im in one kernel with each
operand tile loaded once (docs/future_work.md item 2); tests/test_v5e_compile.py
compiles the kernel for a described v5e. These tests pin interpret-mode
correctness against complex128 numpy, the vmap path the chunked
executor uses, eligibility gating, and the per-step fallback inside
``apply_step_split``.
"""

import numpy as np

import jax

from tnc_tpu.ops.pallas_complex import (
    MIN_FLOPS,
    _tile,
    eligible,
    fused_complex_dot_kl,
    ineligible_reason,
)


def test_tile_selection():
    assert _tile(256, 128, 8) == 128
    assert _tile(64, 128, 8) == 64
    assert _tile(96, 128, 8) == 96  # 96 divides itself
    assert _tile(100, 128, 8) == 100 or _tile(100, 128, 8) is None
    assert _tile(4, 128, 8) is None  # below the f32 sublane floor


def test_tile_boundary_shapes():
    # exact tile floor: the floor itself is a valid tile
    assert _tile(8, 128, 8) == 8
    assert _tile(128, 128, 128) == 128
    assert _tile(7, 128, 8) is None  # just under the floor
    # non-multiple dims: falls through halvings until a divisor ≥ floor
    assert _tile(96, 64, 8) == 32  # 96 % 64 != 0 → 32 divides
    assert _tile(12, 128, 8) == 12
    assert _tile(10, 128, 8) == 10
    assert _tile(9, 128, 8) == 9  # odd but ≥ floor and divides itself
    # k = 1 degenerate: no tile ≥ any floor > 1 exists
    assert _tile(1, 512, 8) is None
    assert _tile(1, 512, 1) == 1


def test_eligibility_gate():
    assert eligible(1024, 256, 256)
    assert not eligible(8, 8, 128)  # too small to amortize the grid
    assert not eligible(1024, 4, 256)  # M below sublane floor


def test_eligibility_boundary_shapes():
    # k = 1 degenerate: big enough flops, but K can't tile
    assert not eligible(1, 4096, 4096)
    assert ineligible_reason(1, 4096, 4096) == "tile_floor"
    # exactly at the flop floor: 2*k*m*n == MIN_FLOPS is eligible
    k = m = n = 128
    assert 2 * k * m * n == MIN_FLOPS
    assert eligible(k, m, n)
    assert not eligible(k, m, n - 1)  # one element under
    assert ineligible_reason(k, m, n - 1) == "flop_floor"
    # N below its 128 lane floor even when flops clear
    assert ineligible_reason(4096, 4096, 64) == "tile_floor"
    assert ineligible_reason(4096, 4096, 128) is None


def _rand(shape, rng):
    return rng.standard_normal(shape).astype(np.float32)


def test_fused_matches_complex128_oracle():
    rng = np.random.default_rng(0)
    K, M, N = 1024, 256, 384
    ar, ai = _rand((K, M), rng), _rand((K, M), rng)
    br, bi = _rand((K, N), rng), _rand((K, N), rng)
    re, im = jax.jit(
        lambda a, b, c, d: fused_complex_dot_kl(a, b, c, d, interpret=True)
    )(ar, ai, br, bi)
    want = (ar + 1j * ai).astype(np.complex128).T @ (br + 1j * bi).astype(
        np.complex128
    )
    got = np.asarray(re) + 1j * np.asarray(im)
    assert got.shape == (M, N)
    denom = float(np.max(np.abs(want)))
    assert float(np.max(np.abs(got - want))) / denom < 1e-5


def test_fused_vmap_matches():
    """The chunked executor vmaps the step kernel over slice batches."""
    rng = np.random.default_rng(1)
    B, K, M, N = 2, 512, 128, 128
    ar, ai = _rand((B, K, M), rng), _rand((B, K, M), rng)
    br, bi = _rand((B, K, N), rng), _rand((B, K, N), rng)
    re, im = jax.jit(
        jax.vmap(
            lambda a, b, c, d: fused_complex_dot_kl(a, b, c, d, interpret=True)
        )
    )(ar, ai, br, bi)
    want = np.einsum(
        "bkm,bkn->bmn",
        (ar + 1j * ai).astype(np.complex128),
        (br + 1j * bi).astype(np.complex128),
    )
    got = np.asarray(re) + 1j * np.asarray(im)
    denom = float(np.max(np.abs(want)))
    assert float(np.max(np.abs(got - want))) / denom < 1e-5


def test_fused_mode_end_to_end_with_fallback(monkeypatch):
    """TNC_TPU_COMPLEX_MULT=fused through a real program: eligible steps
    take the kernel (interpret mode off-TPU), the rest fall back to
    naive dots, and the whole-program result matches the oracle."""
    from tnc_tpu.builders.connectivity import ConnectivityLayout
    from tnc_tpu.builders.random_circuit import random_circuit
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.ops.backends import JaxBackend, NumpyBackend
    from tnc_tpu.ops.program import build_program, flat_leaf_tensors

    monkeypatch.setenv("TNC_TPU_COMPLEX_MULT", "fused")
    rng = np.random.default_rng(7)
    tn = random_circuit(
        12, 6, 0.4, 0.4, rng, ConnectivityLayout.LINE, bitstring="*" * 12
    )
    result = Greedy(OptMethod.GREEDY).find_path(tn)
    program = build_program(tn, result.replace_path())
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]

    want = NumpyBackend(dtype=np.complex128).execute(program, arrays)
    got = JaxBackend(
        dtype="complex64", split_complex=True, precision="float32"
    ).execute(program, arrays)
    denom = max(float(np.max(np.abs(want))), 1e-30)
    assert float(np.max(np.abs(got - want))) / denom < 1e-5


def test_fused_path_actually_engages(monkeypatch):
    """A big eligible contraction must route through the kernel (guards
    against the eligibility gate silently sending everything to the
    naive fallback)."""
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.ops import pallas_complex
    from tnc_tpu.ops.backends import JaxBackend, NumpyBackend
    from tnc_tpu.ops.program import build_program, flat_leaf_tensors
    from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor
    from tnc_tpu.tensornetwork.tensordata import TensorData

    monkeypatch.setenv("TNC_TPU_COMPLEX_MULT", "fused")
    calls = []
    real = pallas_complex.fused_complex_dot_kl

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(pallas_complex, "fused_complex_dot_kl", counting)

    rng = np.random.default_rng(3)
    shared = list(range(10))          # 2^10 contracted
    a_free = list(range(10, 17))      # 2^7 free
    b_free = list(range(17, 24))      # 2^7 free
    def leaf(legs):
        shape = [2] * len(legs)
        data = (rng.standard_normal(shape) + 1j * rng.standard_normal(shape))
        return LeafTensor(legs, [2] * len(legs), TensorData.matrix(data / 32.0))
    tn = CompositeTensor([leaf(shared + a_free), leaf(shared + b_free)])
    program = build_program(tn, ContractionPath.simple([(0, 1)]))
    arrays = [l.data.into_data() for l in flat_leaf_tensors(tn)]

    want = NumpyBackend(dtype=np.complex128).execute(program, arrays)
    got = JaxBackend(
        dtype="complex64", split_complex=True, precision="float32"
    ).execute(program, arrays)
    assert calls, "fused kernel was never invoked"
    denom = max(float(np.max(np.abs(want))), 1e-30)
    assert float(np.max(np.abs(got - want))) / denom < 1e-5


def test_fused_fallback_counter_carries_reason(monkeypatch):
    """Every per-step fused fallback is counted with its eligibility
    reason (ops.fused_fallback{reason=...}) — the satellite that makes
    'fused silently did nothing' visible in bench records."""
    from tnc_tpu import obs
    from tnc_tpu.ops.backends import JaxBackend
    from tnc_tpu.ops.program import build_program, flat_leaf_tensors
    from tnc_tpu.builders.connectivity import ConnectivityLayout
    from tnc_tpu.builders.random_circuit import random_circuit
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod

    monkeypatch.setenv("TNC_TPU_COMPLEX_MULT", "fused")
    obs.configure(enabled=True, registry=obs.MetricsRegistry())
    try:
        rng = np.random.default_rng(2)
        tn = random_circuit(
            8, 4, 0.4, 0.4, rng, ConnectivityLayout.LINE, bitstring="*" * 8
        )
        program = build_program(
            tn, Greedy(OptMethod.GREEDY).find_path(tn).replace_path()
        )
        arrays = [l.data.into_data() for l in flat_leaf_tensors(tn)]
        JaxBackend(
            dtype="complex64", split_complex=True, precision="float32"
        ).execute(program, arrays)
        counters = obs.get_registry().snapshot()["counters"]
    finally:
        obs.configure(enabled=False)
    reasons = {
        k for k in counters if k.startswith("ops.fused_fallback{")
    }
    # every step of this tiny program is under the flop floor
    assert any("reason=flop_floor" in k or "reason=layout" in k
               for k in reasons), counters


# -- fused multi-step chains --------------------------------------------


def _chain_program(seed=0, qubits=10, depth=5):
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
    arrays = [l.data.into_data() for l in flat_leaf_tensors(tn)]
    return program, arrays


def test_chain_groups_structure():
    """Grouping invariants: spans cover ≥2 consecutive steps, never
    overlap, each step after the head consumes the running slot, and a
    big step (over the flop bound) breaks the run."""
    from tnc_tpu.ops.program import chain_groups, step_flops

    program, _ = _chain_program()
    groups = chain_groups(program.steps)
    assert groups, "no chains found in a residual-style program"
    prev_end = 0
    for s, e in groups:
        assert e - s >= 2
        assert s >= prev_end
        prev_end = e
        run_slot = program.steps[s].lhs
        for i in range(s + 1, e):
            st = program.steps[i]
            assert run_slot in (st.lhs, st.rhs)
            run_slot = st.lhs
    # a zero flop bound admits nothing
    assert chain_groups(program.steps, max_flops=0.0) == ()
    # a tiny element budget admits nothing
    assert chain_groups(program.steps, max_elems=1.0) == ()
    # sanity: every grouped step really is small
    for s, e in groups:
        for i in range(s, e):
            assert step_flops(program.steps[i]) <= 1 << 22


def test_chain_interpret_bit_parity_vs_sequential_naive():
    """The fused chain kernel in interpret mode is BIT-identical to
    the same sequence of naive f32 dots run unfused as plain jax ops
    (``fused_chain_reference`` — the sequential-loop arithmetic): the
    kernel fuses dispatches, it must not move a single bit."""
    import jax.numpy as jnp

    from tnc_tpu.ops.pallas_complex import (
        ChainLink,
        fused_chain_kl,
        fused_chain_reference,
    )

    rng = np.random.default_rng(13)

    def f32(*shape):
        return jnp.asarray(
            rng.standard_normal(shape).astype(np.float32)
        )

    # 3-step chain: (8,16)x(8,4) -> Z(16,4); carried as (8,8)
    # contract-first; then carried as (4,8) contract-first on the
    # second operand side
    first_ops = (f32(8, 16), f32(8, 16), f32(8, 4), f32(8, 4))
    link_ops = [
        (f32(8, 4), f32(8, 4)),
        (f32(4, 16), f32(4, 16)),
    ]
    links = [
        ChainLink(True, (8, 8), 0),
        ChainLink(False, (4, 8), 0),
    ]
    got_r, got_i = fused_chain_kl(
        first_ops, link_ops, links, interpret=True
    )
    want_r, want_i = fused_chain_reference(first_ops, link_ops, links)
    assert got_r.shape == want_r.shape == (16, 8)
    assert np.array_equal(np.asarray(got_r), np.asarray(want_r))
    assert np.array_equal(np.asarray(got_i), np.asarray(want_i))


def test_chain_fused_vs_unfused_policy_allclose():
    """Whole-program: the fused chain policy against the same modes
    with chains stripped — fusion must hold the f32 parity target end
    to end (reduction orders may differ across GEMM shapes, so this is
    the allclose pin; the bitwise pin lives at kernel granularity)."""
    import jax.numpy as jnp

    from tnc_tpu.ops.backends import place_buffers
    from tnc_tpu.ops.split_complex import (
        KernelPolicy,
        combine_array,
        plan_kernels,
        run_steps_split,
    )

    program, arrays = _chain_program(seed=13)
    policy = plan_kernels(program, force="chain")
    assert policy.chains

    buffers = place_buffers(arrays, "complex64", True)
    fused = run_steps_split(
        jnp, program, buffers, "float32", policy=policy, interpret=True
    )
    seq_policy = KernelPolicy(policy.modes, ())
    buffers = place_buffers(arrays, "complex64", True)
    seq = run_steps_split(
        jnp, program, buffers, "float32", policy=seq_policy
    )
    got = np.asarray(combine_array(*fused))
    want = np.asarray(combine_array(*seq))
    denom = max(float(np.max(np.abs(want))), 1e-30)
    assert float(np.max(np.abs(got - want))) / denom < 1e-6


def test_chain_under_jit_matches_oracle(monkeypatch):
    """Whole-program jit with TNC_TPU_COMPLEX_MULT=chain: chains fuse
    inside the trace and the result holds the f32 parity target."""
    from tnc_tpu.ops.backends import JaxBackend, NumpyBackend

    monkeypatch.setenv("TNC_TPU_COMPLEX_MULT", "chain")
    program, arrays = _chain_program(seed=21)
    want = NumpyBackend(dtype=np.complex128).execute(program, arrays)
    got = JaxBackend(
        dtype="complex64", split_complex=True, precision="float32"
    ).execute(program, arrays)
    denom = max(float(np.max(np.abs(want))), 1e-30)
    assert float(np.max(np.abs(got - want))) / denom < 1e-5


def test_chain_vmap_matches_singletons(monkeypatch):
    """execute_batched (the serving batch path) under chain mode: the
    vmapped chain kernel equals per-entry execution."""
    from tnc_tpu.ops.backends import JaxBackend

    monkeypatch.setenv("TNC_TPU_COMPLEX_MULT", "chain")
    program, arrays = _chain_program(seed=8, qubits=8, depth=4)
    backend = JaxBackend(
        dtype="complex64", split_complex=True, precision="float32"
    )
    B = 3
    stacked = list(arrays)
    stacked[0] = np.stack([arrays[0]] * B)
    batched = backend.execute_batched(program, stacked, [0])
    single = backend.execute(program, arrays)
    assert batched.shape[0] == B
    for i in range(B):
        np.testing.assert_allclose(
            batched[i], single, rtol=0, atol=np.max(np.abs(single)) * 1e-6
        )


def test_chain_host_oracle_matches_naive():
    """On the host (numpy) split path, chained steps run the
    sequential naive loop — bit-identical to an unpoliced naive run."""
    from tnc_tpu.ops.split_complex import (
        combine_array,
        plan_kernels,
        run_steps_split,
        split_array,
    )

    from tnc_tpu.ops.split_complex import KernelPolicy

    program, arrays = _chain_program(seed=4, qubits=8, depth=4)
    policy = plan_kernels(program, force="chain")
    buffers = [split_array(a, "float64") for a in arrays]
    with_policy = combine_array(
        *run_steps_split(np, program, buffers, policy=policy)
    )
    # same modes, chains stripped — fusion is the only difference
    buffers = [split_array(a, "float64") for a in arrays]
    without = combine_array(
        *run_steps_split(
            np, program, buffers, policy=KernelPolicy(policy.modes, ())
        )
    )
    assert np.array_equal(with_policy, without)
