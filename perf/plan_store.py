#!/usr/bin/env python3
"""The plan store of a configuration that reads its plan from one.

    python3 perf/plan_store.py --config sycamore53_m20_share [--out DIR]

Plans the configuration's circuit once, with its stated planner block at
its ``target_log2``, and writes the entry into the program's own plan
store (``tnc_tpu.serve.plancache.PlanCache``) at the configuration's
``plan_store`` directory (or ``--out``): path pairs, slicing, hoist split
and the program signature, keyed by the network's structure digest. A
fleet plans once and ships that entry; a cell's set-up reads it back
read-only (``tnc_tpu.serve.plancache.load_stored_plan``) and never plans.

The structure is the same for every seed (only gate data and the
bitstring change), so the network is built from seed 0 and bitstring
0...0. Runs on the CPU; the planner's trial pool re-imports this file, so
nothing runs at import time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

from perf import common  # noqa: E402

HBM_BYTES = 16 << 30  # one TPU v5e chip


def network(config: dict, seed: int = 0, bits: str | None = None):
    """``(gates, bitstring, simplified network)`` of the configuration's
    amplitude for ``seed`` (bitstring 0...0 unless given)."""
    from perf import circuits, sut
    from tnc_tpu.tensornetwork.simplify import simplify_network

    spec = config["circuit"]
    gates = circuits.circuit_gates(spec, seed)
    bits = bits if bits is not None else "0" * spec["qubits"]
    raw, _ = sut.build_circuit(gates, spec["qubits"]).into_amplitude_network(bits)
    return gates, bits, simplify_network(raw)


def plan_entry(config: dict, tn, cache, hbm_bytes: int = HBM_BYTES) -> tuple[str, dict]:
    """``(key, record)``: the configuration's planner block on ``tn`` at
    its target, the joint search's slice set handed on to the repair,
    the residual checked against one chip's memory by the budget model."""
    from perf import sut
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.contractionpath.slicing import slice_and_reconfigure, sliced_flops
    from tnc_tpu.ops.budget import fits_hbm, program_peak_bytes
    from tnc_tpu.ops.hoist import hoist_sliced_program
    from tnc_tpu.ops.sliced import build_sliced_program

    target = 2.0 ** config["target_log2"]
    inputs = list(tn.tensors)
    t0 = time.monotonic()
    planner = sut.make_planner(config["planner"], target)
    result = planner.find_path(tn)
    joint = planner.last_slicing
    common.progress("plan_store", "search", t0, path_cmacs=float(result.flops),
                    joint_legs=None if joint is None else len(joint.legs))
    t1 = time.monotonic()
    pairs, slicing = slice_and_reconfigure(
        inputs, result.ssa_path.toplevel, target,
        seed_slices=joint.legs if joint is not None else None,
    )
    path = ContractionPath.simple(pairs)
    sp = build_sliced_program(tn, path, slicing)
    hp = hoist_sliced_program(sp)
    if not fits_hbm(hp.residual.program, batch=1, hbm_bytes=hbm_bytes):
        raise SystemExit(f"the residual of the plan at 2^{config['target_log2']} does not "
                         f"fit {hbm_bytes} bytes by the budget model: lower target_log2")
    key = cache.key_for_network(tn, target)
    record = cache.record_for(
        path, sp.program, slicing, sliced_program=sp,
        flops=result.flops, peak=result.size, finder=config["planner"]["finder"],
        target_size=target,
    )
    total = float(sliced_flops(inputs, path.toplevel, slicing))
    record.update(
        structure=key, config=config["name"], planner=config["planner"],
        target_log2=config["target_log2"], joint_slicing=joint is not None,
        num_slices=slicing.num_slices, sliced_cmacs=total,
        cmacs_per_slice=total / slicing.num_slices,
        residual_peak_bytes=program_peak_bytes(hp.residual.program).peak_bytes,
        fusion=slicing.fusion,  # stem fusion's [before, after] a slice
        plan_s=time.monotonic() - t0,
    )
    common.progress("plan_store", "slice", t1, legs=len(slicing.legs),
                    num_slices=slicing.num_slices, sliced_cmacs=total)
    return key, record


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", required=True)
    parser.add_argument("--out", help="store directory (default: the configuration's plan_store)")
    args = parser.parse_args(argv)
    config = common.load_json("configs", f"{args.config}.json")
    out = args.out or os.path.join(CHECKOUT, config["plan_store"])

    from tnc_tpu.serve.plancache import PlanCache

    cache = PlanCache(out)
    _, _, tn = network(config)
    key, record = plan_entry(config, tn, cache)
    cache.store(key, record)
    print(json.dumps({"stored": os.path.join(out, f"{key}.json"),
                      **{k: record[k] for k in ("num_slices", "sliced_cmacs",
                                                "cmacs_per_slice", "residual_peak_bytes",
                                                "plan_s", "joint_slicing")}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
