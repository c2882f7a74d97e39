"""The system under test, as the benchmark calls it.

Everything that imports ``tnc_tpu`` lives here and in ``perf/traffic/``:
circuit → network → plan → sliced program, through the entry points a
user calls. The set-up helpers are copies of ``chip_smoke.py``'s
(``plan_sliced``), not imports of them. Nothing here touches JAX at
import time: the planner's trial pool re-imports the main module.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

from perf import common


def enable_compile_cache() -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` if set, else
    at the fixed ``<checkout>/.cache/jax_cache``. The program's own helper
    makes the same choice (``tnc_tpu/utils/compile_cache.py``); it is
    called so that code of the program that asks it agrees."""
    from tnc_tpu.utils.compile_cache import enable_compile_cache as enable

    return enable()


def build_circuit(gates, n_qubits: int):
    """The benchmark's gate list through the program's circuit builder
    and gate library, by name."""
    from tnc_tpu.builders.circuit_builder import Circuit
    from tnc_tpu.tensornetwork.tensordata import TensorData

    circuit = Circuit()
    reg = circuit.allocate_register(n_qubits)
    made = {}
    for name, params, qubits in gates:
        key = (name, tuple(params))
        if key not in made:
            made[key] = (
                TensorData.gate(name, tuple(params)) if params
                else TensorData.gate(name)
            )
        circuit.append_gate(made[key], [reg.qubit(q) for q in qubits])
    return circuit


def make_planner(planner: dict, target_size: float):
    from tnc_tpu.contractionpath.paths.hyper import Hyperoptimizer

    if planner["finder"] != "Hyperoptimizer":
        raise ValueError(f"unknown planner {planner['finder']!r}")
    return Hyperoptimizer(
        seed=planner["seed"], target_size=target_size,
        ntrials=planner["ntrials"],
        reconfigure_budget=planner["reconfigure_budget"],
        polish_rounds=planner["polish_rounds"],
    )


@dataclass
class SlicedPlan:
    """One planned, sliced network and what the benchmark reads of it."""

    tn: object
    path: object
    slicing: object
    sp: object  # SlicedProgram
    hp: object  # HoistedProgram
    arrays: list
    info: dict = field(default_factory=dict)

    @property
    def num_slices(self) -> int:
        return self.slicing.num_slices

    def question(self) -> dict:
        """What the plain reference is told of the plan: leaf legs, the
        pair order, the sliced legs. Names and sizes, no data."""
        from tnc_tpu.ops.program import flat_leaf_tensors

        leaves = flat_leaf_tensors(self.tn)
        leg_dims = {}
        for leaf in leaves:
            leg_dims.update(dict(leaf.edges()))
        return {
            "leaf_legs": [tuple(leaf.legs) for leaf in leaves],
            "pairs": [(st.lhs, st.rhs) for st in self.sp.program.steps],
            "sliced_legs": tuple(self.slicing.legs),
            "sliced_dims": tuple(self.slicing.dims),
            "leg_dims": leg_dims,
        }


def plan_sliced(gates, n_qubits: int, bitstring: str, config: dict, device=None) -> SlicedPlan:
    """Network of one amplitude, simplified, planned with the
    configuration's planner and sliced to the first target from
    ``target_log2`` down whose per-slice residual the program's HBM
    budget model accepts (as ``chip_smoke.py::plan_sliced``)."""
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.contractionpath.slicing import slice_and_reconfigure, sliced_flops
    from tnc_tpu.ops.budget import fits_hbm
    from tnc_tpu.ops.hoist import hoist_sliced_program
    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.ops.sliced import build_sliced_program
    from tnc_tpu.tensornetwork.simplify import simplify_network

    raw, _ = build_circuit(gates, n_qubits).into_amplitude_network(bitstring)
    n_raw = len(raw)
    tn = simplify_network(raw)
    inputs = list(tn.tensors)
    target_log2 = config["target_log2"]
    t_plan = time.monotonic()
    while True:
        target = 2.0 ** target_log2
        t0 = time.monotonic()
        result = make_planner(config["planner"], target).find_path(tn)
        pairs, slicing = slice_and_reconfigure(inputs, result.ssa_path.toplevel, target)
        path = ContractionPath.simple(pairs)
        sp = build_sliced_program(tn, path, slicing)
        hp = hoist_sliced_program(sp)
        fits = fits_hbm(hp.residual.program, batch=1, device=device)
        common.progress(
            "plan", f"target 2^{target_log2}", t0,
            num_slices=slicing.num_slices, fits_hbm=fits,
        )
        if fits:
            break
        target_log2 -= 1
    info = {
        "network": f"{n_raw} tensors -> {len(tn)} after simplify",
        "target_log2": target_log2,
        "plan_s": time.monotonic() - t_plan,
        "path_cmacs": float(result.flops),
        "sliced_cmacs": float(sliced_flops(inputs, path.toplevel, slicing)),
        "num_slices": slicing.num_slices,
        "sliced_legs": len(slicing.legs),
        "steps": len(sp.program.steps),
        "prelude_steps": len(hp.prelude_steps),
        "residual_steps": len(hp.residual.program.steps),
        "structure_digest": common.digest(
            [sorted(leaf.legs) for leaf in flat_leaf_tensors(tn)]
        ),
        "plan_digest": common.digest(
            [[(st.lhs, st.rhs) for st in sp.program.steps],
             list(slicing.legs), list(slicing.dims)]
        ),
    }
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]
    return SlicedPlan(tn, path, slicing, sp, hp, arrays, info)


def plan_for(run, device=None):
    """``(gates, bitstring, plan)`` of a sliced-amplitude cell for its seed."""
    from perf import circuits

    spec = run.config["circuit"]
    gates = circuits.circuit_gates(spec, run.seed)
    bits = circuits.seeded_bitstrings(1, spec["qubits"], run.seed)[0]
    with common.span("plan"):
        plan = plan_sliced(gates, spec["qubits"], bits, run.config, device)
    common.emit({"phase": "plan", **plan.info})
    return gates, bits, plan


def result_to_complex(result, split_complex: bool):
    """A device-resident executor result (a (real, imag) pair in split
    mode) as a host complex128 array."""
    import numpy as np

    if split_complex:
        re, im = result
        return np.asarray(re, dtype=np.float64) + 1j * np.asarray(im, dtype=np.float64)
    return np.asarray(result).astype(np.complex128)
