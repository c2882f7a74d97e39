"""Compensated accumulation + complex-multiply lowering accuracy.

The sliced executors accumulate thousands of per-slice contributions
whose total cancels to far below the individual terms; plain f32
accumulation loses the 1e-5 parity target there (reference accuracy
contract: ``tnc/tests/integration_tests.rs`` epsilon assertions).
These tests pin down that:

- ``kahan_add`` actually compensates (XLA must not algebraically cancel
  ``y - (t - s)`` under jit — it doesn't: XLA preserves FP semantics
  unless fast-math flags are set);
- the ``naive`` 4-dot complex-multiply mode matches the oracle at least
  as tightly as the Gauss 3-dot mode;
- both sliced executors stay oracle-exact with the compensated path.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tnc_tpu.ops.sliced import kahan_add


def test_kahan_add_compensates_under_jit():
    # 1.0 followed by many tiny terms: plain f32 summation drops them
    # entirely (1 + 1e-8 == 1 in f32); Kahan keeps them to ~1 ulp.
    n = 4096
    tiny = np.float32(1e-8)
    exact = 1.0 + float(n) * 1e-8

    def plain(n):
        def body(_, s):
            return s + tiny

        return jax.lax.fori_loop(0, n, body, jnp.float32(1.0))

    def compensated(n):
        def body(_, sc):
            return kahan_add(sc[0], sc[1], tiny)

        s, c = jax.lax.fori_loop(
            0, n, body, (jnp.float32(1.0), jnp.float32(0.0))
        )
        return s + c

    plain_err = abs(float(jax.jit(plain, static_argnums=0)(n)) - exact)
    kahan_err = abs(float(jax.jit(compensated, static_argnums=0)(n)) - exact)
    assert plain_err > 1e-5  # f32 really does lose the tail
    assert kahan_err < 1e-7  # and compensation survives XLA

    # cancellation pattern: +x, -x, ... + tiny residue
    xs = np.zeros(2000, dtype=np.float32)
    xs[0::2] = 777.77
    xs[1::2] = -777.77
    xs = np.concatenate([xs, np.full(100, 1e-4, dtype=np.float32)])

    def ksum(v):
        def body(sc, x):
            return kahan_add(sc[0], sc[1], x), None

        (s, c), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)), v)
        return s + c

    got = float(jax.jit(ksum)(jnp.asarray(xs)))
    assert got == pytest.approx(0.01, rel=1e-5)


@pytest.mark.parametrize("mode", ["gauss", "naive"])
def test_complex_mult_modes_match_oracle(mode, monkeypatch):
    from tnc_tpu.builders.random_circuit import random_circuit
    from tnc_tpu.builders.connectivity import ConnectivityLayout
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.ops.backends import JaxBackend, NumpyBackend
    from tnc_tpu.ops.program import build_program, flat_leaf_tensors

    monkeypatch.setenv("TNC_TPU_COMPLEX_MULT", mode)
    rng = np.random.default_rng(7)
    tn = random_circuit(
        8, 6, 0.4, 0.4, rng, ConnectivityLayout.LINE, bitstring="*" * 8
    )
    result = Greedy(OptMethod.GREEDY).find_path(tn)
    program = build_program(tn, result.replace_path())
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]

    want = NumpyBackend(dtype=np.complex128).execute(program, arrays)
    got = JaxBackend(
        dtype="complex64", split_complex=True, precision="float32"
    ).execute(program, arrays)
    denom = max(float(np.max(np.abs(want))), 1e-30)
    err = float(np.max(np.abs(got - want))) / denom
    assert err < 5e-6


# batch 1: every slice goes through the compensated fold on its own
@pytest.mark.parametrize("slice_batch", [4, 1])
def test_sliced_executors_with_kahan_match_oracle(slice_batch, monkeypatch):
    from tnc_tpu.builders.random_circuit import random_circuit
    from tnc_tpu.builders.connectivity import ConnectivityLayout
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.contractionpath.slicing import slice_and_reconfigure
    from tnc_tpu.ops.backends import JaxBackend
    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.ops.sliced import build_sliced_program, execute_sliced_numpy

    monkeypatch.setenv("TNC_TPU_COMPLEX_MULT", "naive")
    rng = np.random.default_rng(11)
    tn = random_circuit(
        10, 5, 0.5, 0.4, rng, ConnectivityLayout.LINE, bitstring="0" * 10
    )
    result = Greedy(OptMethod.GREEDY).find_path(tn)
    inputs = list(tn.tensors)
    for divisor in (8.0, 4.0, 2.0):
        try:
            replace_pairs, slicing = slice_and_reconfigure(
                inputs, result.ssa_path.toplevel, max(result.size / divisor, 2.0),
                # the divisors rely on the slicer refusing slicings too
                # large for this test to run
                max_slices=1 << 26,
            )
            break
        except ValueError:
            continue
    else:
        pytest.skip("instance would not slice at any tried target")
    if slicing.num_slices <= 1:
        pytest.skip("instance did not slice")
    sp = build_sliced_program(
        tn, ContractionPath.simple(replace_pairs), slicing
    )
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]
    want = execute_sliced_numpy(sp, arrays, dtype=np.complex128)

    backend = JaxBackend(
        dtype="complex64",
        split_complex=True,
        precision="float32",
        slice_batch=slice_batch,
        chunk_steps=8,
    )
    got = np.asarray(backend.execute_sliced(sp, arrays))
    denom = max(float(np.max(np.abs(want))), 1e-30)
    assert float(np.max(np.abs(got - want))) / denom < 1e-5

    # subset mode (partial sums) stays consistent too
    want_sub = execute_sliced_numpy(
        sp, arrays, dtype=np.complex128, max_slices=3
    )
    got_sub = np.asarray(backend.execute_sliced(sp, arrays, max_slices=3))
    assert float(np.max(np.abs(got_sub - want_sub))) / denom < 1e-5


def test_parallel_oracle_pool_path_matches_serial():
    """The spawn-pool oracle path (workers=2 forced, so the pool branch
    runs even on a 1-core host) must agree exactly with the serial
    oracle, and per-slice partials must sum to the full result."""
    from tnc_tpu.builders.random_circuit import random_circuit
    from tnc_tpu.builders.connectivity import ConnectivityLayout
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.contractionpath.slicing import slice_and_reconfigure
    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.ops.sliced import (
        build_sliced_program,
        execute_sliced_numpy,
        execute_sliced_numpy_parallel,
        sliced_partials_numpy,
    )

    rng = np.random.default_rng(11)
    tn = random_circuit(
        10, 5, 0.5, 0.4, rng, ConnectivityLayout.LINE, bitstring="0" * 10
    )
    result = Greedy(OptMethod.GREEDY).find_path(tn)
    inputs = list(tn.tensors)
    for divisor in (8.0, 4.0, 2.0):
        try:
            replace_pairs, slicing = slice_and_reconfigure(
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
    sp = build_sliced_program(
        tn, ContractionPath.simple(replace_pairs), slicing
    )
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]

    want = execute_sliced_numpy(sp, arrays, dtype=np.complex128)
    got = execute_sliced_numpy_parallel(sp, arrays, dtype=np.complex128, workers=2)
    assert np.allclose(got, want, rtol=1e-13, atol=1e-300)

    parts = sliced_partials_numpy(
        sp, arrays, dtype=np.complex128, slice_ids=[0, 1], workers=2
    )
    serial = sliced_partials_numpy(
        sp, arrays, dtype=np.complex128, slice_ids=[0, 1], workers=1
    )
    assert parts.shape == serial.shape
    assert np.allclose(parts, serial, rtol=1e-13, atol=1e-300)

    # subset parallel sum == serial subset sum
    want_sub = execute_sliced_numpy(sp, arrays, dtype=np.complex128, max_slices=2)
    got_sub = execute_sliced_numpy_parallel(
        sp, arrays, dtype=np.complex128, max_slices=2, workers=2
    )
    assert np.allclose(got_sub, want_sub, rtol=1e-13, atol=1e-300)
