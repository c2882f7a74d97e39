#!/usr/bin/env python3
"""Smoke of tnc_tpu on the chip: does today's default path still start?

``python chip_smoke.py`` needs one TPU and drives, through the entry
points a user calls, in ONE process:

- ``device``         what JAX sees, the compile cache in use, the native
                     planner library;
- ``ghz``            the README quick start (``contract_tensor_network(...,
                     backend="jax")``): a 20-qubit GHZ amplitude and a
                     20-qubit depth-12 random-circuit statevector against
                     the numpy backend;
- ``sycamore53_m14`` the Sycamore-53 m=14 amplitude, planned here from
                     nothing on disk, every slice on the chip through
                     ``JaxBackend().execute_sliced`` (default kernel
                     policy, hoisting, chunked executor), sampled whole
                     slices against the numpy complex128 backend;
- ``serve``          ``ContractionService.from_circuit`` on the 53-qubit
                     circuit at depth ``SERVE_DEPTH`` (sliced) with the
                     JAX backend, three amplitude requests through
                     ``submit``.

``python chip_smoke.py --chips 4`` runs only the four-chip paths
(slice-SPMD on the same plan, ``SPMD_SLICES_PER_CHIP`` slices per chip;
the partitioned fan-in on 4-partition networks), each against the
one-chip result computed in the same run.

Every phase prints one JSON line (phase, seconds, what was compared and
the error found); any failure is fatal and exits non-zero. The last
line is ``{"ok": true, "device": {...}}``. This is a smoke, not a
speed: the seconds say where a cold run goes, nothing more. Without a
TPU it fails; it never pins or falls back to the CPU.

Nothing at import time touches JAX: the planner's trial pool spawns
workers that re-import this file, and they must stay off the chip.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
import traceback

import numpy as np

QUBITS = 53
DEPTH = 14
SEED = 42
PARITY = 1e-5
#: First slicing target tried (halved until the HBM budget model accepts
#: the per-slice program). The budget model accepts 2^29 on a v5e, but
#: the 1200 s this script is allowed do not: XLA:TPU needs 9 minutes to
#: compile the two chunk programs of a 2^29 plan (one step alone, a
#: rank-19 transpose over 2^26 elements, takes 4) against 2 at 2^25.
TARGET_LOG2 = 25
#: Slices of the m=14 plan this script runs: a contiguous prefix. Every
#: slice does not fit any time limit — measured on the v5e, a slice of
#: this script's 8-trial plan moves ~23 GB through HBM and takes 38 ms
#: at 2^25 (the chip runs it at its memory roofline), so the plan's
#: 262144 slices are hours of device time. Width, depth and the
#: per-slice program are real; the count of slices is what is cut, and
#: the seconds per slice are printed with the projection.
M14_SLICES = 1024
#: Depth of the served 53-qubit circuit: every request on a sliced
#: structure runs the whole slice loop, so the deepest depth whose
#: request ends in seconds is served (8 slices at depth 10; depth 12
#: is 1024 slices of 2^29 per request, depth 14 hours). Width is not cut.
SERVE_DEPTH = 10
#: Slices per chip of the four-chip slice-SPMD run (a contiguous prefix
#: of the plan's slices, compared with the same prefix on one chip).
SPMD_SLICES_PER_CHIP = 256
#: planner settings for both 53-qubit structures — far below bench.py's
#: 128 trials on purpose (printed with each plan: this is not a speed).
#: Work-bounded (no wall-clock budget), so the plan — and with it every
#: compile-cache key — is the same on every run and machine.
#: Depth of the 53-qubit network of the partitioned fan-in (4 partitions:
#: the cut legs between them bound the fan-in tensors — 2^35 elements
#: already at depth 8) and the per-slice target of its globally sliced
#: variant. Depth 5, not 6: in f32 the depth-6 amplitude cancels to the
#: edge of the 1e-5 comparison (split-mode CPU rehearsal: 3e-6 plain,
#: 6e-6..1.6e-5 globally sliced, which sums slices without Kahan
#: compensation); depth 5 rehearses at 1e-6 and 2e-6.
FANIN_DEPTH = 5
FANIN_SLICED_TARGET_LOG2 = 18
PLANNER = {
    "ntrials": 8,
    "reconfigure_budget": None,
    "polish_rounds": 2,
}
PLANNER_INFO = {"finder": "Hyperoptimizer", "seed": SEED, **PLANNER}


class SmokeFailure(AssertionError):
    """A phase compared two things and they disagreed."""


def emit(record: dict) -> None:
    print(json.dumps(record, default=str), flush=True)


def progress(phase: str, step: str, t0: float, **info) -> None:
    """One line as a step of a phase ends, so that a run cut by its time
    limit still says how far it got and where the time went."""
    emit(
        {
            "phase": phase, "step": step,
            "seconds": round(time.monotonic() - t0, 3), **info,
        }
    )


def final_line(platform: str, kind: str, count: int) -> str:
    """The last line of a passing run, to the driver's contract."""
    return json.dumps(
        {"ok": True, "device": {"platform": platform, "kind": kind, "count": count}}
    )


def run_phase(name: str, fn, *args, **kwargs) -> dict:
    """Run one phase, print its JSON line; a failure propagates."""
    t0 = time.monotonic()
    out = fn(*args, **kwargs)
    emit({"phase": name, "seconds": round(time.monotonic() - t0, 3), **out})
    return out


def check(err: float, limit: float, what: str) -> float:
    if not (math.isfinite(err) and err <= limit):
        raise SmokeFailure(f"{what}: error {err!r} exceeds {limit!r}")
    return err


def rel_err(got, want) -> float:
    """Max abs difference relative to the reference's largest magnitude."""
    got = np.asarray(got).reshape(-1)
    want = np.asarray(want).reshape(-1)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    scale = max(float(np.max(np.abs(want))), 1e-300)
    return float(np.max(np.abs(got - want))) / scale


def planner(target_size: float):
    from tnc_tpu.contractionpath.paths.hyper import Hyperoptimizer

    return Hyperoptimizer(seed=SEED, target_size=target_size, **PLANNER)


def assert_on_platform(tree, platform: str) -> int:
    """Every array of ``tree`` lives only on ``platform`` devices."""
    import jax

    leaves = jax.tree.leaves(tree)
    for leaf in leaves:
        where = {d.platform for d in leaf.devices()}
        if where != {platform}:
            raise SmokeFailure(
                f"result array lives on {sorted(where)}, expected {platform}"
            )
    return len(leaves)


# -- phases -------------------------------------------------------------


def phase_device() -> dict:
    import jax

    from tnc_tpu.partitioning.native_binding import load_native
    from tnc_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    stats = dev.memory_stats() or {}
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "count": len(devices),
        "bytes_limit": stats.get("bytes_limit"),
        "jax": jax.__version__,
        "compile_cache_dir": cache_dir,
        "native_planner_loaded": load_native() is not None,
    }


def phase_ghz(backend="jax", qubits: int = 20, depth: int = 12) -> dict:
    """The README quick start on two small networks."""
    from tnc_tpu.builders.circuit_builder import Circuit
    from tnc_tpu.builders.connectivity import ConnectivityLayout
    from tnc_tpu.builders.random_circuit import random_circuit
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.tensornetwork.contraction import contract_tensor_network
    from tnc_tpu.tensornetwork.tensordata import TensorData

    circuit = Circuit()
    reg = circuit.allocate_register(qubits)
    circuit.append_gate(TensorData.gate("h"), [reg.qubit(0)])
    for i in range(qubits - 1):
        circuit.append_gate(
            TensorData.gate("cx"), [reg.qubit(i), reg.qubit(i + 1)]
        )
    tn, _ = circuit.into_amplitude_network("1" * qubits)
    path = Greedy(OptMethod.GREEDY).find_path(tn).replace_path()
    out = contract_tensor_network(tn, path, backend=backend)
    amp = complex(np.asarray(out.data.into_data()).reshape(-1)[0])
    ghz_err = check(
        abs(amp - 1.0 / math.sqrt(2.0)), PARITY, "GHZ amplitude vs 1/sqrt(2)"
    )

    sv_tn = random_circuit(
        qubits, depth, 0.4, 0.4, np.random.default_rng(SEED),
        ConnectivityLayout.SYCAMORE, bitstring="*" * qubits,
    )
    sv_path = Greedy(OptMethod.GREEDY).find_path(sv_tn).replace_path()
    got = contract_tensor_network(sv_tn, sv_path, backend=backend)
    want = contract_tensor_network(sv_tn, sv_path, backend="numpy")
    sv_err = check(
        rel_err(got.data.into_data(), want.data.into_data()), PARITY,
        "random-circuit statevector vs numpy backend",
    )
    return {
        "ghz_qubits": qubits,
        "ghz_amplitude": [amp.real, amp.imag],
        "ghz_abs_err": ghz_err,
        "statevector": f"random {qubits}q depth {depth}",
        "statevector_rel_err_vs_numpy": sv_err,
        "limit": PARITY,
    }


def plan_sliced(tn, target_log2: int, device=None):
    """Plan ``tn`` and slice it to the first target, from ``target_log2``
    down, whose per-slice residual the HBM budget model accepts."""
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.contractionpath.slicing import (
        slice_and_reconfigure,
        sliced_flops,
    )
    from tnc_tpu.ops.budget import fits_hbm
    from tnc_tpu.ops.hoist import hoist_sliced_program
    from tnc_tpu.ops.sliced import build_sliced_program

    inputs = list(tn.tensors)
    while True:
        target = 2.0**target_log2
        t0 = time.monotonic()
        result = planner(target).find_path(tn)
        pairs, slicing = slice_and_reconfigure(
            inputs, result.ssa_path.toplevel, target
        )
        path = ContractionPath.simple(pairs)
        sp = build_sliced_program(tn, path, slicing)
        hp = hoist_sliced_program(sp)
        fits = fits_hbm(hp.residual.program, batch=1, device=device)
        progress(
            "plan", f"target 2^{target_log2}", t0,
            num_slices=slicing.num_slices, fits_hbm=fits,
        )
        if fits:
            break
        target_log2 -= 1
    info = {
        "planner": PLANNER_INFO,
        "target_log2": target_log2,
        "planning_s": round(time.monotonic() - t0, 3),
        "path_flops": result.flops,
        "sliced_flops": sliced_flops(inputs, path.toplevel, slicing),
        "sliced_legs": len(slicing.legs),
        "num_slices": slicing.num_slices,
        "steps": len(sp.program.steps),
        "prelude_steps": len(hp.prelude_steps),
        "residual_steps": len(hp.residual.program.steps),
    }
    return path, slicing, sp, hp, info


def kernel_mode_mix(hp) -> dict:
    """Modes the default policy gives the residual steps (what the
    chunked executor plans per chunk) and the hoisted prelude steps."""
    from collections import Counter

    from tnc_tpu.ops.split_complex import (
        auto_step_mode,
        plan_kernels,
        resolved_step_mode,
    )

    policy = plan_kernels(hp.residual.program)
    prelude = Counter(
        auto_step_mode(ps.step) or resolved_step_mode(ps.step)
        for ps in hp.prelude_steps
    )
    return {
        "residual": dict(Counter(policy.modes)),
        "residual_chains": len(policy.chains),
        "prelude": dict(prelude),
    }


def phase_sliced_amplitude(
    circuit, bitstring: str, backend, platform: str,
    target_log2: int = TARGET_LOG2, sample_seed: int = SEED,
    max_slices: int | None = None,
) -> dict:
    """Plan, run the slices on ``backend`` (all of them, or the first
    ``max_slices``) with the result kept on the device, and compare one
    seeded batch of whole slices with the numpy complex128 backend."""
    import jax

    from tnc_tpu import obs
    from tnc_tpu.ops.backends import NumpyBackend
    from tnc_tpu.ops.budget import clamp_slice_batch
    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.ops.split_complex import combine_array
    from tnc_tpu.tensornetwork.simplify import simplify_network

    raw, _ = circuit.into_amplitude_network(bitstring)
    tn = simplify_network(raw)
    _, slicing, sp, hp, info = plan_sliced(tn, target_log2, backend.device)
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]
    n_run = min(max_slices or slicing.num_slices, slicing.num_slices)

    obs.configure(enabled=True, registry=obs.MetricsRegistry())
    try:
        t0 = time.monotonic()
        on_device = backend.execute_sliced(
            sp, arrays, host=False, max_slices=n_run
        )
        jax.block_until_ready(on_device)
        cold_s = time.monotonic() - t0
        progress("sliced_amplitude", f"cold run, {n_run} slices", t0)
        n_arrays = assert_on_platform(on_device, platform)
        t0 = time.monotonic()
        again = backend.execute_sliced(
            sp, arrays, host=False, max_slices=n_run
        )
        jax.block_until_ready(again)
        warm_s = time.monotonic() - t0
        progress("sliced_amplitude", f"warm run, {n_run} slices", t0)

        # one aligned batch of whole slices, chosen from the seed — the
        # executor's own batch, so the chunk programs are the ones the
        # full run compiled
        batch = clamp_slice_batch(
            hp.residual.program, backend.slice_batch, device=backend.device,
            split_complex=backend.split_complex,
        )
        while n_run % batch:
            batch -= 1
        rng = np.random.default_rng(sample_seed)
        lo = batch * int(rng.integers(n_run // batch))
        sample = (lo, lo + batch)
        t0 = time.monotonic()
        got = backend.execute_sliced(sp, arrays, slice_range=sample)
        progress("sliced_amplitude", f"slices {sample} on the backend", t0)
        t0 = time.monotonic()
        want = NumpyBackend(dtype=np.complex128).execute_sliced(
            sp, arrays, slice_range=sample
        )
        reference_s = time.monotonic() - t0
        progress("sliced_amplitude", f"slices {sample} on numpy", t0)
        counters = {
            **obs.counters_by_prefix("ops."),
            **obs.counters_by_prefix("resilience."),
            **obs.counters_by_prefix("hbm."),
        }
    finally:
        obs.configure(enabled=False, registry=obs.MetricsRegistry())

    sample_err = check(
        rel_err(got, want), PARITY,
        f"slices {sample} vs numpy complex128 backend",
    )
    if backend.split_complex:
        full = combine_array(*on_device)
        full_again = combine_array(*again)
    else:
        full, full_again = np.asarray(on_device), np.asarray(again)
    full = np.asarray(full).reshape(sp.program.result_shape)
    if not np.all(np.isfinite(full)) or not np.any(full):
        raise SmokeFailure(f"summed amplitude is not finite and non-zero: {full}")
    repeat_err = check(
        rel_err(full_again, full), PARITY, "second full run vs first"
    )
    amp = complex(full.reshape(-1)[0])
    return {
        "network": f"{len(raw)} tensors -> {len(tn)} after simplify",
        **info,
        "slice_batch": batch,
        "slices_run": n_run,
        "warm_ms_per_slice": round(1e3 * warm_s / n_run, 4),
        "projected_all_slices_s": round(
            warm_s / n_run * slicing.num_slices, 1
        ),
        "kernel_modes": kernel_mode_mix(hp),
        "counters": counters,
        "result_arrays_on": platform,
        "result_arrays": n_arrays,
        "cold_run_s": round(cold_s, 3),
        "warm_run_s": round(warm_s, 3),
        "partial_amplitude": [amp.real, amp.imag],
        "repeat_rel_err": repeat_err,
        "sampled_slices": list(sample),
        "sampled_slices_n": batch,
        "reference": "numpy complex128, unhoisted slice loop",
        "reference_s": round(reference_s, 3),
        "sample_rel_err": sample_err,
        "limit": PARITY,
    }


def phase_serve(
    circuit, bitstrings, backend, target_log2: int | None = TARGET_LOG2,
    depth: int | None = None,
) -> dict:
    """Three amplitude requests through ``ContractionService.submit``,
    each against a direct ``execute_sliced`` (``execute`` when the
    structure needs no slicing) on that bitstring's bound network."""
    import jax

    from tnc_tpu import obs
    from tnc_tpu.serve.rebind import stacked_bras
    from tnc_tpu.serve.service import ContractionService

    target = None if target_log2 is None else 2.0**target_log2
    obs.configure(enabled=True, registry=obs.MetricsRegistry())
    t0 = time.monotonic()
    svc = ContractionService.from_circuit(
        circuit,
        pathfinder=planner(target) if target is not None else None,
        target_size=target,
        backend=backend,
    )
    try:
        bind_s = time.monotonic() - t0
        progress("serve", "plan + bind", t0)
        bound = svc.bound
        answers, request_s = [], []
        for bits in bitstrings:
            t0 = time.monotonic()
            answers.append(complex(svc.submit(bits).result()))
            request_s.append(round(time.monotonic() - t0, 3))
            progress("serve", f"request {len(answers)}", t0)
        stats = svc.stats()
        counts = stats["counts"]
        if (
            counts["completed"] != len(bitstrings)
            or counts["failed"]
            or counts["degraded_batches"]
        ):
            raise SmokeFailure(f"service counts: {counts}")
        counters = {
            **obs.counters_by_prefix("serve.rebind."),
            **obs.counters_by_prefix("backend.execute_sliced_calls"),
            **obs.counters_by_prefix("ops."),
            **obs.counters_by_prefix("resilience."),
        }
    finally:
        svc.stop()
        obs.configure(enabled=False, registry=obs.MetricsRegistry())

    errs = []
    for bits, answer in zip(bitstrings, answers):
        arrays = list(bound.arrays)
        bras = stacked_bras([bound.template.request_bits(bits)])[0]
        for i, slot in enumerate(bound.bra_slots):
            arrays[slot] = bras[i]
        if bound.sliced is not None:
            direct = backend.execute_sliced(bound.sliced, arrays)
        else:
            direct = backend.execute(bound.program, arrays)
        direct = complex(np.asarray(direct).reshape(-1)[0])
        if not (math.isfinite(abs(answer)) and abs(direct) > 0.0):
            raise SmokeFailure(f"served {answer!r} / direct {direct!r}")
        errs.append(
            check(
                abs(answer - direct) / abs(direct), PARITY,
                f"served amplitude of {bits} vs direct execution",
            )
        )
    sliced = bound.sliced
    return {
        "qubits": len(bitstrings[0]),
        "depth": depth,
        "backend": type(backend).__name__,
        "backend_device": str(backend.device or jax.devices()[0]),
        "planner": PLANNER_INFO if target is not None
        else "Greedy",
        "target_log2": target_log2,
        "plan_bind_s": round(bind_s, 3),
        "sliced": sliced is not None,
        "num_slices": sliced.slicing.num_slices if sliced is not None else 1,
        "steps": len(bound.program.steps),
        "requests": list(bitstrings),
        "amplitudes": [[a.real, a.imag] for a in answers],
        "request_s": request_s,
        "rel_err_vs_direct": errs,
        "limit": PARITY,
        "counters": counters,
        "stats": stats,
    }


def seeded_bitstrings(n: int, qubits: int, seed: int = SEED) -> list[str]:
    rng = np.random.default_rng(seed)
    return [
        "".join(str(b) for b in rng.integers(0, 2, size=qubits))
        for _ in range(n)
    ]


# -- four chips ----------------------------------------------------------


def peak_bytes_per_device() -> list:
    import jax

    return [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.devices()
    ]


def phase_slice_spmd(
    circuit, bitstring: str, n_devices: int, platform: str,
    target_log2: int = TARGET_LOG2,
    slices_per_device: int = SPMD_SLICES_PER_CHIP,
) -> dict:
    """Slice-SPMD over ``n_devices`` — ``slices_per_device`` slices
    each, a contiguous prefix of the plan's slices — against the
    one-chip ``execute_sliced`` over the same prefix."""
    import jax

    from tnc_tpu.ops.backends import JaxBackend
    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.parallel.sliced_parallel import (
        distributed_sliced_contraction,
        make_mesh,
    )
    from tnc_tpu.tensornetwork.simplify import simplify_network

    raw, _ = circuit.into_amplitude_network(bitstring)
    tn = simplify_network(raw)
    dev0 = jax.devices()[0]
    path, slicing, sp, _, info = plan_sliced(tn, target_log2, dev0)
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]
    mesh = make_mesh(n_devices)
    if {d.platform for d in mesh.devices.flat} != {platform}:
        raise SmokeFailure(f"mesh is not on {platform}: {mesh.devices}")
    per_device = min(slices_per_device, slicing.num_slices // n_devices)
    prefix = per_device * n_devices
    t0 = time.monotonic()
    out = distributed_sliced_contraction(
        tn, path, slicing, mesh=mesh, hoist=True, max_slices=prefix
    )
    spmd_s = time.monotonic() - t0
    progress("slice_spmd", f"{prefix} slices over {n_devices} devices", t0)
    # read before the one-chip reference runs on the first device
    peaks = peak_bytes_per_device()
    t0 = time.monotonic()
    one = np.asarray(
        JaxBackend(device=dev0).execute_sliced(sp, arrays, max_slices=prefix)
    )
    one_chip_s = time.monotonic() - t0
    progress("slice_spmd", f"{prefix} slices on one chip", t0)
    err = check(
        rel_err(out.data.into_data(), one), PARITY,
        f"slice-SPMD over {n_devices} devices vs one-chip execute_sliced",
    )
    return {
        **info,
        "devices": n_devices,
        "slices_per_device": per_device,
        "slices_run": prefix,
        "one_chip_s": round(one_chip_s, 3),
        "spmd_cold_s": round(spmd_s, 3),
        "rel_err_vs_one_chip": err,
        "limit": PARITY,
        "peak_bytes_in_use_after_spmd": peaks,
    }


def phase_partitioned(
    circuit, qubits: int, n_devices: int,
    sliced_target_log2: int = FANIN_SLICED_TARGET_LOG2,
) -> dict:
    """The partitioned fan-in over ``n_devices`` partitions of one
    network — unsliced, and with global slicing and a per-slice fan-in —
    each against the one-chip ``execute_sliced`` on the flat network."""
    import jax

    from tnc_tpu import CompositeTensor
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.contractionpath.slicing import slice_and_reconfigure
    from tnc_tpu.ops.backends import JaxBackend
    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.ops.sliced import build_sliced_program
    from tnc_tpu.parallel.partitioned import (
        distributed_partitioned_contraction,
        distributed_partitioned_sliced_contraction,
    )
    from tnc_tpu.tensornetwork.partitioning import (
        find_partitioning,
        partition_tensor_network,
    )
    from tnc_tpu.tensornetwork.simplify import simplify_network

    raw, _ = circuit.into_amplitude_network("0" * qubits)
    tn = simplify_network(raw)
    grouped = partition_tensor_network(
        CompositeTensor(list(tn.tensors)), find_partitioning(tn, n_devices)
    )
    path = Greedy(OptMethod.GREEDY).find_path(grouped).replace_path()

    t0 = time.monotonic()
    plain = distributed_partitioned_contraction(
        grouped, path, n_devices=n_devices
    )
    plain_s = time.monotonic() - t0
    progress("partitioned_fanin", "plain", t0)
    t0 = time.monotonic()
    sliced, slicing = distributed_partitioned_sliced_contraction(
        grouped, path, n_devices=n_devices,
        target_size=2.0**sliced_target_log2,
    )
    sliced_s = time.monotonic() - t0
    progress(
        "partitioned_fanin", f"sliced: {slicing.num_slices} global slices", t0
    )
    # cumulative over the process (a peak never resets); read before
    # the one-chip reference runs on the first device
    peaks = peak_bytes_per_device()

    flat = Greedy(OptMethod.GREEDY).find_path(tn)
    pairs, flat_slicing = slice_and_reconfigure(
        list(tn.tensors), flat.ssa_path.toplevel, flat.size / 16.0
    )
    sp = build_sliced_program(tn, ContractionPath.simple(pairs), flat_slicing)
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]
    want = JaxBackend(device=jax.devices()[0]).execute_sliced(sp, arrays)
    return {
        "devices": n_devices,
        "tensors": len(tn),
        "partitions": len(grouped),
        "plain_s": round(plain_s, 3),
        "plain_rel_err_vs_one_chip": check(
            rel_err(plain.data.into_data(), want), PARITY,
            "partitioned fan-in vs one-chip execute_sliced",
        ),
        "global_slices": slicing.num_slices,
        "sliced_s": round(sliced_s, 3),
        "sliced_rel_err_vs_one_chip": check(
            rel_err(sliced.data.into_data(), want), PARITY,
            "globally sliced partitioned fan-in vs one-chip execute_sliced",
        ),
        "one_chip_slices": flat_slicing.num_slices,
        "limit": PARITY,
        "peak_bytes_in_use": peaks,
    }


# -- entry ---------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the four-chip paths and their one-chip references",
    )
    args = parser.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke.py needs a TPU; JAX reports {len(devices)} "
            f"{dev.platform!r} device(s)"
        )
    if len(devices) < args.chips:
        raise SystemExit(
            f"--chips {args.chips} needs {args.chips} devices, "
            f"JAX reports {len(devices)}"
        )

    from tnc_tpu.builders.sycamore_circuit import sycamore_circuit
    from tnc_tpu.ops.backends import JaxBackend

    def circuit(depth=DEPTH):
        return sycamore_circuit(QUBITS, depth, np.random.default_rng(SEED))

    t_start = time.monotonic()
    run_phase("device", phase_device)
    if args.chips == 4:
        run_phase(
            "slice_spmd", phase_slice_spmd, circuit(), "0" * QUBITS, 4, "tpu"
        )
        run_phase(
            "partitioned_fanin", phase_partitioned,
            circuit(FANIN_DEPTH), QUBITS, 4,
        )
    else:
        run_phase("ghz", phase_ghz, "jax")
        backend = JaxBackend()
        run_phase(
            "sycamore53_m14", phase_sliced_amplitude,
            circuit(), "0" * QUBITS, backend, "tpu",
            max_slices=M14_SLICES,
        )
        run_phase(
            "serve", phase_serve,
            circuit(SERVE_DEPTH), seeded_bitstrings(3, QUBITS), backend,
            depth=SERVE_DEPTH,
        )
    emit({"phase": "total", "seconds": round(time.monotonic() - t_start, 3)})
    print(final_line(dev.platform, dev.device_kind, len(devices)), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException:
        traceback.print_exc()
        sys.stdout.flush()
        sys.exit(1)
    sys.exit(code)
