"""Single-chip TPU hardware tier.

The analogue of the reference's real-MPI integration tier
(``tnc/tests/integration_tests.rs:121-167``, which self-launches under
real MPI ranks): these tests run the contraction, split-complex, and
sliced execution paths on a *real accelerator* and pin complex64 parity
against the numpy oracle to 1e-5 (the BASELINE.md requirement).

Run:  TNC_TPU_TEST_PLATFORM=tpu python -m pytest -m tpu tests/

They skip (not fail) under the default CPU-pinned suite so `pytest`
stays green on CPU-only hosts; the bench machine runs them as the
pre-bench smoke.
"""

import os

import numpy as np
import pytest

pytestmark = pytest.mark.tpu

requires_tpu_env = pytest.mark.skipif(
    os.environ.get("TNC_TPU_TEST_PLATFORM", "cpu") == "cpu",
    reason="hardware tier: set TNC_TPU_TEST_PLATFORM=tpu",
)


@pytest.fixture(scope="module")
def device():
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        pytest.skip("no accelerator available")
    return dev


def _ghz_circuit(n):
    from tnc_tpu.builders.circuit_builder import Circuit
    from tnc_tpu.tensornetwork.tensordata import TensorData

    c = Circuit()
    reg = c.allocate_register(n)
    c.append_gate(TensorData.gate("h"), [reg.qubit(0)])
    for i in range(n - 1):
        c.append_gate(TensorData.gate("cx"), [reg.qubit(i), reg.qubit(i + 1)])
    return c


def _ghz_network(n=16):
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod

    tn, _ = _ghz_circuit(n).into_amplitude_network("1" * n)
    result = Greedy(OptMethod.GREEDY).find_path(tn)
    return tn, result


def _hbm_scale_program():
    """A deterministic instance whose greedy program peaks at ~2^29
    bytes split-complex (2^26 elements) — big enough that HBM budget
    questions are meaningful, small enough to compile on a 16 GB v5e.
    LINE-layout circuits cannot serve here: their chain structure keeps
    greedy peaks near 2^20 bytes at any qubit count, so the budget
    tests would assert on toys (measured round 4)."""
    from tnc_tpu.builders.connectivity import ConnectivityLayout
    from tnc_tpu.builders.random_circuit import random_circuit
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.ops.program import build_program
    from tnc_tpu.tensornetwork.simplify import simplify_network

    rng = np.random.default_rng(4)
    tn = simplify_network(
        random_circuit(
            32, 10, 0.5, 0.5, rng, ConnectivityLayout.SYCAMORE,
            bitstring="0" * 32,
        )
    )
    result = Greedy(OptMethod.GREEDY).find_path(tn)
    return tn, build_program(tn, result.replace_path())


@requires_tpu_env
def test_whole_path_contraction_parity(device):
    """complex64 split-complex whole-path program vs numpy oracle."""
    from tnc_tpu.tensornetwork.contraction import contract_tensor_network

    tn, result = _ghz_network()
    got = complex(
        contract_tensor_network(tn, result.replace_path(), backend="jax")
        .data.into_data()
    )
    want = complex(
        contract_tensor_network(tn, result.replace_path(), backend="numpy")
        .data.into_data()
    )
    assert abs(got - want) <= 1e-5 * max(1.0, abs(want))


@requires_tpu_env
def test_random_circuit_statevector_parity(device):
    """Wider program: 12q random-circuit statevector, max-abs parity."""
    from tnc_tpu.builders.connectivity import ConnectivityLayout
    from tnc_tpu.builders.random_circuit import random_circuit
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.ops.backends import JaxBackend, NumpyBackend
    from tnc_tpu.ops.program import build_program, flat_leaf_tensors

    rng = np.random.default_rng(7)
    tn = random_circuit(
        12, 8, 0.5, 0.5, rng, ConnectivityLayout.LINE, bitstring="*" * 12
    )
    result = Greedy(OptMethod.GREEDY).find_path(tn)
    program = build_program(tn, result.replace_path())
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]
    got = np.asarray(JaxBackend(dtype="complex64").execute(program, arrays))
    want = np.asarray(NumpyBackend(np.complex128).execute(program, arrays))
    denom = max(float(np.max(np.abs(want))), 1e-30)
    assert float(np.max(np.abs(got - want))) / denom <= 1e-5


@requires_tpu_env
def test_sliced_execution_parity(device):
    """On-device slice loop (both strategies) vs numpy sliced oracle.

    Runs on a random SYCAMORE-layout amplitude network (4.7M-element
    greedy peak, 16 slices at an 8x target) — GHZ/LINE chains cannot
    serve here: their peaks are tens of elements, so any slicing target
    degenerates into millions of do-nothing slices (measured round 5;
    the round-4 red tier was this degenerate instance raising in
    ``find_slicing``)."""
    from tnc_tpu.builders.connectivity import ConnectivityLayout
    from tnc_tpu.builders.random_circuit import random_circuit
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.contractionpath.slicing import find_slicing
    from tnc_tpu.ops.backends import JaxBackend
    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.ops.sliced import build_sliced_program, execute_sliced_numpy
    from tnc_tpu.tensornetwork.simplify import simplify_network

    rng = np.random.default_rng(4)
    tn = simplify_network(
        random_circuit(
            20, 10, 0.5, 0.5, rng, ConnectivityLayout.SYCAMORE,
            bitstring="0" * 20,
        )
    )
    result = Greedy(OptMethod.GREEDY).find_path(tn)
    replace = result.replace_path()
    inputs = list(tn.tensors)
    slicing = find_slicing(inputs, replace.toplevel, result.size / 8.0)
    assert 2 <= slicing.num_slices <= 64, slicing.num_slices
    sp = build_sliced_program(tn, replace, slicing)
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]
    want = execute_sliced_numpy(sp, arrays, dtype=np.complex128)
    got = np.asarray(JaxBackend(dtype="complex64").execute_sliced(sp, arrays))
    denom = max(float(np.max(np.abs(want))), 1e-30)
    assert float(np.max(np.abs(got - want))) / denom <= 1e-5


@requires_tpu_env
def test_donation_keeps_result_correct_on_repeat(device):
    """Donated buffers: running the same jitted program twice from fresh
    host arrays must give identical results (no use-after-donate)."""
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.ops.backends import JaxBackend
    from tnc_tpu.ops.program import build_program, flat_leaf_tensors

    tn, result = _ghz_network(10)
    program = build_program(tn, result.replace_path())
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]
    backend = JaxBackend(dtype="complex64")
    first = np.asarray(backend.execute(program, arrays))
    second = np.asarray(backend.execute(program, arrays))
    np.testing.assert_array_equal(first, second)


@requires_tpu_env
def test_compiled_peak_matches_budget_model(device):
    """Near-HBM-scale compile: XLA's measured footprint must stay within
    ~1.5x of the budget model's padded prediction — the regression test
    for an early benchmark failure, where a 2.1 GB logical buffer compiled to
    a 34 GB tile-padded allocation."""
    import jax

    from tnc_tpu.ops.budget import compiled_peak_bytes, program_peak_bytes
    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.ops.split_complex import run_steps_split

    # ~2^26-element intermediates: a significant fraction of v5e HBM
    tn, program = _hbm_scale_program()
    est = program_peak_bytes(program, split_complex=True, batch=1)
    assert est.peak_bytes > 1 << 28, "test network too small to be meaningful"

    leaves = flat_leaf_tensors(tn)
    specs = tuple(
        (
            jax.ShapeDtypeStruct(tuple(leaf.bond_dims), np.float32),
            jax.ShapeDtypeStruct(tuple(leaf.bond_dims), np.float32),
        )
        for leaf in leaves
    )

    def fn(buffers):
        import jax.numpy as jnp

        return run_steps_split(jnp, program, list(buffers), "float32")

    compiled = compiled_peak_bytes(fn, (specs,))
    # compiled footprint must not blow past the model (that
    # failure mode was a ~16x overshoot)
    assert compiled <= est.peak_bytes * 1.5, (compiled, est.peak_bytes)


@requires_tpu_env
def test_staged_prep_parity_on_device(device):
    """The staged operand prep (lane permutation via one-hot MXU matmul,
    tile-safe transposes) on a real accelerator: a big operand with
    contract/free legs alternating in storage — the naive prep's
    worst case — must match the host oracle to 1e-5."""
    from tnc_tpu.ops.backends import apply_step
    from tnc_tpu.ops.program import _pair_step
    from tnc_tpu.ops.split_complex import apply_step_split, split_array
    from tnc_tpu.tensornetwork.tensor import LeafTensor

    import jax.numpy as jnp

    c = [1, 2, 3, 4, 5]
    f = [6, 7, 8, 9, 10]
    legs_a = [c[0], f[0], c[1], f[1], c[2], f[2], c[3], f[3], c[4], f[4]]
    ta = LeafTensor(legs_a, [4] * 10)  # 1M elements: staged prep fires
    tb = LeafTensor([c[4], c[3], c[2], c[1], c[0], 11], [4] * 6)
    step, _ = _pair_step(0, 1, ta, tb)
    assert step.a_ops is not None, "premise: the big operand must stage"

    rng = np.random.default_rng(0)
    a = (
        rng.standard_normal(4**10) + 1j * rng.standard_normal(4**10)
    ).reshape([4] * 10)
    b = (
        rng.standard_normal(4**6) + 1j * rng.standard_normal(4**6)
    ).reshape([4] * 6)
    want = np.asarray(
        apply_step(np, a.astype(np.complex128), b.astype(np.complex128), step)
    )
    ar, ai = split_array(a)
    br, bi = split_array(b)
    re, im = apply_step_split(
        jnp,
        (jnp.asarray(ar), jnp.asarray(ai)),
        (jnp.asarray(br), jnp.asarray(bi)),
        step,
        precision="float32",
    )
    got = np.asarray(re) + 1j * np.asarray(im)
    scale = float(np.max(np.abs(want)))
    assert float(np.max(np.abs(got - want))) / scale <= 1e-5


@requires_tpu_env
def test_amplitude_sweep_on_device(device):
    """Batched amplitude sweep on hardware: one compiled program, GHZ
    analytic values."""
    import math

    from tnc_tpu.tensornetwork.sweep import amplitude_sweep

    n = 12
    bits = ["0" * n, "1" * n, "01" * (n // 2)]
    amps = amplitude_sweep(_ghz_circuit(n), bits)
    r = 1 / math.sqrt(2)
    assert abs(amps[0] - r) <= 1e-5 and abs(amps[1] - r) <= 1e-5
    assert abs(amps[2]) <= 1e-6


@requires_tpu_env
def test_budget_clamp_prevents_oom_scale_batches(device):
    """The chunked executor's auto-clamp must reduce an oversized batch
    request to one that fits the real device's HBM."""
    from tnc_tpu.ops.budget import clamp_slice_batch, device_hbm_bytes

    tn, program = _hbm_scale_program()
    hbm = device_hbm_bytes(device)
    clamped = clamp_slice_batch(program, 4096, device=device)
    # a 4096-wide batch of 2^26-element intermediates cannot fit 16-32 GB
    assert clamped < 4096
    from tnc_tpu.ops.budget import fits_hbm

    assert fits_hbm(program, batch=clamped, hbm_bytes=hbm)


@pytest.mark.tpu
def test_naive_mult_kahan_bench_arithmetic_parity(device):
    """The benchmark's exact arithmetic on device — naive 4-dot complex
    multiply + Kahan-compensated slice accumulation at
    precision='float32' — vs the complex128 oracle, on a deep sliced
    program (the round-4 parity mechanisms)."""
    from tnc_tpu.builders.connectivity import ConnectivityLayout
    from tnc_tpu.builders.random_circuit import random_circuit
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.contractionpath.slicing import slice_and_reconfigure
    from tnc_tpu.ops.backends import JaxBackend
    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.ops.sliced import build_sliced_program, execute_sliced_numpy

    rng = np.random.default_rng(11)
    tn = random_circuit(
        14, 8, 0.5, 0.4, rng, ConnectivityLayout.LINE, bitstring="0" * 14
    )
    result = Greedy(OptMethod.GREEDY).find_path(tn)
    inputs = list(tn.tensors)
    for divisor in (16.0, 8.0, 4.0, 2.0):
        try:
            pairs, slicing = slice_and_reconfigure(
                inputs, result.ssa_path.toplevel, max(result.size / divisor, 2.0),
                # the divisors rely on the slicer refusing slicings too
                # large for this test to run
                max_slices=1 << 26,
            )
            break
        except ValueError:
            continue
    else:
        pytest.skip("instance would not slice")
    if slicing.num_slices < 4:
        pytest.skip("instance did not slice deep enough")
    sp = build_sliced_program(tn, ContractionPath.simple(pairs), slicing)
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]
    want = execute_sliced_numpy(sp, arrays, dtype=np.complex128)
    denom = max(float(np.max(np.abs(want))), 1e-30)

    import os

    old = os.environ.get("TNC_TPU_COMPLEX_MULT")
    os.environ["TNC_TPU_COMPLEX_MULT"] = "naive"
    try:
        backend = JaxBackend(
            dtype="complex64",
            split_complex=True,
            precision="float32",
            slice_batch=4,
            chunk_steps=16,
        )
        got = np.asarray(backend.execute_sliced(sp, arrays))
    finally:
        if old is None:
            os.environ.pop("TNC_TPU_COMPLEX_MULT", None)
        else:
            os.environ["TNC_TPU_COMPLEX_MULT"] = old
    assert float(np.max(np.abs(got - want))) / denom <= 1e-5
