#!/usr/bin/env python
"""CI smoke: the fused multi-step chain kernel, interpret mode on CPU.

Runs the ghz3 and random20 bench circuits through the split-complex
step executor twice — once with the chain policy (consecutive small
PairSteps grouped into single Pallas dispatches by
``ops.program.chain_groups``) and once unfused — and asserts, per
circuit:

- the per-step dispatch-span count (measured via the obs ``step[...]``
  spans, whose count IS the dispatch count) is **strictly lower** with
  chain fusion on, and matches the policy's predicted dispatch count;
- the chain kernel really traced and ran (a chain that cannot trace
  fails the run — there is no fallback to the sequential loop);
- the fused result holds parity with the complex128 numpy oracle.

This is the CPU-testable half of the kernel promotion ladder's chain
rung — interpret mode only: the kernel has never compiled for a TPU
(tests/test_v5e_compile.py) and the unforced policy plans no chain;
wired into scripts/check.sh.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.pop("TNC_TPU_COMPLEX_MULT", None)  # the smoke forces per run

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import numpy as np  # noqa: E402

PARITY_TARGET = 2e-5  # f32 interpret-mode vs complex128 oracle


def _ghz3_network():
    from tnc_tpu.io.qasm import import_qasm

    qasm = (
        "OPENQASM 2.0;\n"
        'include "qelib1.inc";\n'
        "qreg q[3];\nh q[0];\ncx q[0], q[1];\ncx q[1], q[2];\n"
    )
    tn, _ = import_qasm(qasm).into_statevector_network()
    return tn


def _random20_network():
    from tnc_tpu.builders.connectivity import ConnectivityLayout
    from tnc_tpu.builders.random_circuit import random_circuit

    rng = np.random.default_rng(42)
    return random_circuit(
        20, 12, 0.4, 0.4, rng, ConnectivityLayout.SYCAMORE,
        bitstring="*" * 20,
    )


def _step_span_count(registry) -> int:
    return sum(
        1 for r in registry.span_records() if r.name.startswith("step[")
    )


def run_one(name: str, tn) -> None:
    import jax
    import jax.numpy as jnp

    from tnc_tpu import obs
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.ops.backends import (
        NumpyBackend,
        place_buffers,
        run_steps_timed,
    )
    from tnc_tpu.ops.program import build_program, flat_leaf_tensors
    from tnc_tpu.ops.split_complex import (
        combine_array,
        interpret_for,
        plan_kernels,
    )

    result = Greedy(OptMethod.GREEDY).find_path(tn)
    program = build_program(tn, result.replace_path())
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]
    policy = plan_kernels(program, force="chain")
    assert policy.chains, (
        f"{name}: chain grouping found no fusable runs in "
        f"{len(program.steps)} steps — the pass regressed"
    )

    def timed_run(pol):
        obs.configure(enabled=True, registry=obs.MetricsRegistry())
        buffers = place_buffers(arrays, "complex64", True)
        out = run_steps_timed(
            jnp, program, buffers, 8.0,
            split_complex=True, precision="float32",
            sync=jax.block_until_ready, policy=pol,
            interpret=interpret_for(),
        )
        reg = obs.get_registry()
        amp = combine_array(*out).reshape(program.result_shape)
        return amp, _step_span_count(reg), reg.snapshot()["counters"]

    fused_amp, fused_spans, counters = timed_run(policy)
    _, unfused_spans, _ = timed_run(None)

    assert fused_spans < unfused_spans, (
        f"{name}: chain fusion did not reduce dispatch spans "
        f"({fused_spans} vs {unfused_spans})"
    )
    assert fused_spans == policy.dispatch_count(), (
        f"{name}: span count {fused_spans} != predicted dispatches "
        f"{policy.dispatch_count()}"
    )
    assert unfused_spans == len(program.steps)

    want = NumpyBackend(dtype=np.complex128).execute(program, arrays)
    denom = max(float(np.max(np.abs(want))), 1e-30)
    err = float(np.max(np.abs(np.asarray(fused_amp) - want))) / denom
    assert err < PARITY_TARGET, f"{name}: parity {err:.2e} >= {PARITY_TARGET}"
    print(
        f"[chain smoke] {name}: {len(program.steps)} steps -> "
        f"{fused_spans} dispatches ({len(policy.chains)} chains, "
        f"parity {err:.1e}) OK"
    )


def main() -> int:
    run_one("ghz3", _ghz3_network())
    run_one("random20", _random20_network())
    print("[chain smoke] PASSED")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
