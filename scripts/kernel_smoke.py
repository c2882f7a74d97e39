#!/usr/bin/env python
"""CI smoke: the fused transpose-matmul kernel rung, interpret mode on CPU.

Builds a transpose-dominated contraction (an operand whose contract
legs interleave its free legs in storage, so the step compiler emits a
macro transpose) plus a small residual circuit, and asserts the three
properties the rung exists for:

- **Bytes honesty**: the step's obs span predicts strictly FEWER HBM
  bytes under the ``fused_transpose`` policy than under naive — the
  deleted materialized-transpose pass
  (``ops.program.step_prep_elems``) is credited, and
  ``kernel_plan_summary`` shows the same per-bucket
  ``pred_bytes_planned < pred_bytes_naive`` invariant
  ``scripts/perf_gate.py`` enforces on bench records.
- **Zero fallbacks on the eligible set**: forcing the rung over the
  eligible step fires the kernel, with no
  ``ops.fused_transpose_fallback`` counts — the gate and the kernel
  agree about what the kernel can take. Ineligible steps fall back
  *counted*, never silently.
- **Parity**: the fused-transpose result holds the f32 target against
  the complex128 numpy oracle, and the kernel is BIT-identical to its
  shared-body reference (``pallas_complex.fused_transpose_reference``)
  on the compiler-built step.

This is the CPU-testable half of the bandwidth rung (the hardware A/B
runs through ``bench.py`` with ``TNC_TPU_COMPLEX_MULT=
fused_transpose``); wired into scripts/check.sh.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.pop("TNC_TPU_COMPLEX_MULT", None)  # the smoke forces per run
os.environ.pop("TNC_TPU_DOT_PRECISION", None)

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import numpy as np  # noqa: E402

PARITY_TARGET = 2e-5  # f32 interpret-mode vs complex128 oracle


def _transposed_network():
    """Two leaves whose shared legs sandwich a free leg in storage:
    the step compiler must emit a rank-3 macro transpose on the first
    operand — exactly the fused-transpose kernel's regime."""
    from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor
    from tnc_tpu.tensornetwork.tensordata import TensorData

    rng = np.random.default_rng(11)

    def leaf(legs, dims):
        data = (
            rng.standard_normal(dims) + 1j * rng.standard_normal(dims)
        ) / 8.0
        return LeafTensor(legs, dims, TensorData.matrix(data))

    # A = [x, m, y] (contract x, y interleaved around free m),
    # B = [x, y, n] (contract legs contiguous)
    return CompositeTensor(
        [leaf([0, 1, 2], [4, 512, 64]), leaf([0, 2, 3], [4, 64, 384])]
    )


def _span_bytes(registry) -> float:
    total = 0.0
    for r in registry.span_records():
        if not r.name.startswith("step["):
            continue
        total += float(r.args.get("bytes_in", 0.0)) + float(
            r.args.get("bytes_out", 0.0)
        )
    return total


def main() -> int:
    import jax
    import jax.numpy as jnp

    from tnc_tpu import obs
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.ops.backends import (
        NumpyBackend,
        place_buffers,
        run_steps_timed,
    )
    from tnc_tpu.ops.pallas_complex import (
        fused_transpose_dot_kl,
        fused_transpose_reference,
    )
    from tnc_tpu.ops.program import (
        build_program,
        flat_leaf_tensors,
        step_prep_elems,
    )
    from tnc_tpu.ops.split_complex import (
        KernelPolicy,
        _fused_transpose_layouts,
        combine_array,
        fused_transpose_ineligible_reason,
        interpret_for,
        kernel_plan_summary,
    )

    tn = _transposed_network()
    program = build_program(tn, ContractionPath.simple([(0, 1)]))
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]
    step = program.steps[0]
    assert step_prep_elems(step) > 0.0, (
        "smoke network no longer produces a transpose-carrying step — "
        "the step compiler changed; rebuild the fixture"
    )
    reason = fused_transpose_ineligible_reason(step)
    assert reason is None, f"eligible fixture step became ineligible: {reason}"

    # -- bit parity: kernel vs shared-body reference on the real step --
    re_s, im_s = [
        np.ascontiguousarray(p).astype(np.float32)
        for p in (arrays[0].real, arrays[0].imag)
    ]
    first_lay, second_lay = _fused_transpose_layouts(step)
    a_pair = (re_s.reshape(step.a_view), im_s.reshape(step.a_view))
    b_re = np.ascontiguousarray(arrays[1].real).astype(np.float32)
    b_im = np.ascontiguousarray(arrays[1].imag).astype(np.float32)
    b_pair = (b_re.reshape(step.b_view), b_im.reshape(step.b_view))
    first, second = (b_pair, a_pair) if step.swap else (a_pair, b_pair)
    got = fused_transpose_dot_kl(
        first[0], first[1], second[0], second[1],
        first_lay, second_lay, interpret=True,
    )
    want = fused_transpose_reference(
        first[0], first[1], second[0], second[1], first_lay, second_lay
    )
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))

    # -- span bytes + fallback counters under both policies ------------
    def timed_run(policy):
        obs.configure(enabled=True, registry=obs.MetricsRegistry())
        buffers = place_buffers(arrays, "complex64", True)
        out = run_steps_timed(
            jnp, program, buffers, 8.0,
            split_complex=True, precision="float32",
            sync=jax.block_until_ready, policy=policy,
            interpret=interpret_for(),
        )
        reg = obs.get_registry()
        amp = combine_array(*out).reshape(program.result_shape)
        return amp, _span_bytes(reg), reg.snapshot()["counters"]

    n = len(program.steps)
    fused_amp, fused_bytes, counters = timed_run(
        KernelPolicy(("fused_transpose",) * n)
    )
    _, naive_bytes, _ = timed_run(KernelPolicy(("naive",) * n))
    fallbacks = {
        k: v
        for k, v in counters.items()
        if k.startswith("ops.fused_transpose_fallback")
    }
    assert not fallbacks, (
        f"fused transpose fell back on the eligible set: {fallbacks}"
    )
    assert fused_bytes < naive_bytes, (
        f"fused rung did not predict fewer HBM bytes "
        f"({fused_bytes:.4g} vs {naive_bytes:.4g})"
    )
    saved = step_prep_elems(step) * 8.0
    assert abs((naive_bytes - fused_bytes) - saved) < 1e-6 * naive_bytes, (
        f"span byte delta {naive_bytes - fused_bytes:.4g} != the "
        f"transpose pass {saved:.4g}"
    )

    # -- the static plan shows the same invariant ----------------------
    kplan = kernel_plan_summary(program, KernelPolicy(("fused_transpose",) * n))
    for name, b in kplan["buckets"].items():
        if b["transpose_steps"]:
            assert b["pred_bytes_planned"] < b["pred_bytes_naive"], (
                f"bucket {name}: planned {b['pred_bytes_planned']} !< "
                f"naive {b['pred_bytes_naive']}"
            )

    # -- parity vs the complex128 oracle -------------------------------
    want_amp = NumpyBackend(dtype=np.complex128).execute(program, arrays)
    denom = max(float(np.max(np.abs(want_amp))), 1e-30)
    err = float(np.max(np.abs(np.asarray(fused_amp) - want_amp))) / denom
    assert err < PARITY_TARGET, f"parity {err:.2e} >= {PARITY_TARGET}"

    print(
        f"[kernel smoke] fused_transpose: {n} step(s), span bytes "
        f"{naive_bytes:.3g} -> {fused_bytes:.3g} "
        f"({fused_bytes / naive_bytes:.2f}x, transpose pass credited), "
        f"0 fallbacks, parity {err:.1e}, bitwise==reference OK"
    )
    print("[kernel smoke] PASSED")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
