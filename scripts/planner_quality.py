#!/usr/bin/env python
"""Plan-quality artifact + regression gate.

Two jobs:

1. **Regenerate PLANNER_QUALITY.json**: native Hyperoptimizer vs Greedy
   on the BASELINE north-star networks (plus slice-and-reconfigure
   overhead at the single-chip target), and — on every run — the fast
   ``gate_networks`` set: small CPU-sized circuits where each network
   records greedy/hyper plan cost AND the calibrated-objective
   comparison (the plan found when the Hyperoptimizer minimizes
   predicted *seconds* under the pinned ``reference_model``, next to
   the flops-objective plan priced under the same model). Timings use
   perf_counter (the round-2 artifact reported greedy "seconds": 0.0
   from a too-coarse timer).

2. **``--gate``**: recompute the fast set and compare per-network plan
   cost (flops, log2 peak, predicted seconds) against a committed
   baseline with the same tolerance discipline as
   ``scripts/perf_gate.py`` (a floor so jitter never fails, a cap so a
   genuine blow-up always does) — plan regressions fail CI exactly
   like runtime regressions. Plan search is deterministic (seeded), so
   the floor mostly absorbs cross-platform numeric tie-breaks.

Usage:
    python scripts/planner_quality.py                      # full regen
    python scripts/planner_quality.py --fast               # gate set only
    python scripts/planner_quality.py --gate PLANNER_QUALITY.json --fast
    python scripts/planner_quality.py --gate BASE.json --fresh FRESH.json

Exit codes (gate mode): 0 pass, 1 plan regression, 2 unusable input.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: pinned pricing constants for the calibrated-objective comparison —
#: a *reference* device (1e11 FLOP/s, 1e10 B/s, 20 us/dispatch), NOT a
#: live fit: the artifact must be reproducible on any machine. Live
#: fits belong to bench.py's ``calibration`` block.
REFERENCE_MODEL = {
    "flops_per_s": 1.0e11,
    "bytes_per_s": 1.0e10,
    "dispatch_overhead_s": 2.0e-5,
}

#: the fast, CPU-sized gate set: deterministic structures small enough
#: for check.sh yet planner-discriminating (greedy vs hyper gaps exist)
GATE_NETWORK_NAMES = ("line20_d12", "brickwork12_d8", "qaoa18_p4")

#: gate-set hyper settings — bounded so one network plans in seconds
GATE_NTRIALS = 4
GATE_POLISH_ROUNDS = 1
GATE_POLISH_STEPS = 500
GATE_TARGET_LOG2 = 14.0

#: the sliced gate set: networks planned under a memory budget TIGHT
#: enough to force real slicing (unlike the 2^14 budget above, which
#: every gate network fits unsliced). Each entry records the classic
#: hyper-then-slice-and-reconfigure pipeline ("post") next to the
#: joint tree+slice search ("joint") on the same trials/seed; the gate
#: enforces joint <= post on every network and strictly better on at
#: least one — the whole point of making slicing a search dimension.
#: name -> (gate network, target_log2)
SLICED_GATE_NETWORKS = {
    "line20_d12_b6": ("line20_d12", 6.0),
    "brickwork12_d8_b7": ("brickwork12_d8", 7.0),
    "brickwork14_d12_b8": ("brickwork14_d12", 8.0),
}

#: pinned joint-SA effort for the sliced gate — deeper than the
#: Hyperoptimizer default (the gate is a quality floor, not a latency
#: budget) and explicit so the artifact reproduces anywhere
GATE_JOINT_SA_STEPS = 2000
GATE_JOINT_SA_ROUNDS = 3

#: pinned effort for the ``fleet_trials`` column: the planner-fleet
#: trial grid (scripts/plansvc_smoke.py runs the same protocol) at the
#: pod's default per-trial depth — the column compares WHERE the trials
#: run (2 processes vs 1), not how deep they search
FLEET_NTRIALS = 4
FLEET_SA_STEPS = 600
FLEET_SA_ROUNDS = 2


def _gate_network(name: str):
    from tnc_tpu.builders.connectivity import ConnectivityLayout
    from tnc_tpu.builders.qaoa_circuit import qaoa_circuit
    from tnc_tpu.builders.random_circuit import (
        brickwork_circuit,
        random_circuit,
    )
    from tnc_tpu.tensornetwork.simplify import simplify_network

    if name == "line20_d12":
        raw = random_circuit(
            20, 12, 0.5, 0.5, np.random.default_rng(3),
            ConnectivityLayout.LINE, bitstring="0" * 20,
        )
    elif name == "brickwork12_d8":
        raw, _ = (
            brickwork_circuit(12, 8, np.random.default_rng(1))
            .into_amplitude_network("0" * 12)
        )
    elif name == "brickwork14_d12":
        # sliced-gate workhorse: peak 2^13 under greedy, so the 2^8
        # budget needs real multi-leg slicing
        raw, _ = (
            brickwork_circuit(14, 12, np.random.default_rng(2))
            .into_amplitude_network("0" * 14)
        )
    elif name == "qaoa18_p4":
        raw, _ = (
            qaoa_circuit(18, 4, np.random.default_rng(7))
            .into_amplitude_network("0" * 18)
        )
    else:
        raise ValueError(f"unknown gate network {name!r}")
    return simplify_network(raw)


def _reference_cost_model():
    from tnc_tpu.obs.calibrate import CalibratedCostModel

    return CalibratedCostModel.from_report(REFERENCE_MODEL)


def _plan_predicted_seconds(tn, result, target_size, objective) -> float:
    """Price a finder's winning plan under ``objective``: sliced (via
    the same work-bounded repair the finders' sliced scoring uses) when
    it exceeds the budget, flat otherwise."""
    import math

    from tnc_tpu.contractionpath.slicing import slice_and_reconfigure
    from tnc_tpu.serve.replan import plan_predicted_cost

    inputs = list(tn.tensors)
    if target_size is not None and result.size > target_size:
        try:
            pairs, slicing = slice_and_reconfigure(
                inputs, result.ssa_path.toplevel, target_size,
                reconf_rounds=1, step_budget=None,
                final_rounds=2, final_budget=None,
                fuse=False,  # a score of the search, in multiply-adds
            )
        except ValueError:
            return math.inf
        return plan_predicted_cost(inputs, pairs, slicing, objective)
    return plan_predicted_cost(
        inputs, result.replace_path().toplevel, None, objective
    )


def measure_gate_network(name: str) -> dict:
    from tnc_tpu.contractionpath.contraction_cost import CalibratedObjective
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.contractionpath.paths.hyper import Hyperoptimizer

    tn = _gate_network(name)
    target = 2.0**GATE_TARGET_LOG2
    model = _reference_cost_model()
    objective = CalibratedObjective(model)

    def hyper(obj=None):
        return Hyperoptimizer(
            ntrials=GATE_NTRIALS,
            seed=42,
            target_size=target,
            polish_rounds=GATE_POLISH_ROUNDS,
            polish_steps=GATE_POLISH_STEPS,
            reconfigure_budget=None,  # work-bounded: reproducible ranking
            objective=obj,
        )

    t0 = time.perf_counter()
    greedy = Greedy(OptMethod.GREEDY).find_path(tn)
    greedy_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    flops_plan = hyper().find_path(tn)
    hyper_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    cal_plan = hyper(objective).find_path(tn)
    cal_s = time.perf_counter() - t0

    flops_plan_seconds = _plan_predicted_seconds(
        tn, flops_plan, target, objective
    )
    cal_plan_seconds = _plan_predicted_seconds(tn, cal_plan, target, objective)

    return {
        "cores": len(tn),
        "target_log2": GATE_TARGET_LOG2,
        "greedy": {
            "flops": greedy.flops,
            "log2_peak": float(np.log2(max(greedy.size, 1))),
            "seconds": round(greedy_s, 3),
        },
        "hyper": {
            "flops": flops_plan.flops,
            "log2_peak": float(np.log2(max(flops_plan.size, 1))),
            "predicted_seconds": flops_plan_seconds,
            "seconds": round(hyper_s, 3),
        },
        "calibrated": {
            "flops": cal_plan.flops,
            "log2_peak": float(np.log2(max(cal_plan.size, 1))),
            "predicted_seconds": cal_plan_seconds,
            "seconds": round(cal_s, 3),
        },
    }


def measure_gate_networks() -> dict:
    out = {}
    for name in GATE_NETWORK_NAMES:
        print(f"measuring gate network {name} ...", flush=True)
        out[name] = measure_gate_network(name)
    return out


def measure_sliced_gate_network(name: str) -> dict:
    """One sliced-gate entry: the classic post-pass pipeline vs the
    joint tree+slice search on the same trials/seed, both finished by
    the same bounded ``slice_and_reconfigure`` repair (cold for post,
    seeded with the joint search's slice set for joint)."""
    from tnc_tpu.contractionpath.contraction_cost import CalibratedObjective
    from tnc_tpu.contractionpath.paths.hyper import Hyperoptimizer
    from tnc_tpu.contractionpath.slicing import (
        hoisted_sliced_flops,
        slice_and_reconfigure,
        sliced_flops,
    )
    from tnc_tpu.serve.replan import plan_predicted_cost

    base, target_log2 = SLICED_GATE_NETWORKS[name]
    tn = _gate_network(base)
    inputs = list(tn.tensors)
    target = 2.0**target_log2
    objective = CalibratedObjective(_reference_cost_model())

    def plan(joint: bool) -> dict:
        t0 = time.perf_counter()
        hy = Hyperoptimizer(
            ntrials=GATE_NTRIALS,
            seed=42,
            target_size=target,
            polish_rounds=GATE_POLISH_ROUNDS,
            polish_steps=GATE_POLISH_STEPS,
            reconfigure_budget=None,  # work-bounded: reproducible
            joint_slicing=joint,
            joint_sa_steps=GATE_JOINT_SA_STEPS,
            joint_sa_rounds=GATE_JOINT_SA_ROUNDS,
        )
        result = hy.find_path(tn)
        seed = hy.last_slicing
        pairs, slicing = slice_and_reconfigure(
            inputs, result.ssa_path.toplevel, target,
            reconf_rounds=1, step_budget=None,
            final_rounds=2, final_budget=None,
            seed_slices=seed.legs if seed is not None else None,
            fuse=False,  # a score of the search, in multiply-adds
        )
        plan_s = time.perf_counter() - t0
        total = sliced_flops(inputs, pairs, slicing)
        _, _, hoisted = hoisted_sliced_flops(inputs, pairs, slicing)
        seconds = plan_predicted_cost(
            inputs, pairs, slicing if slicing.num_slices > 1 else None,
            objective,
        )
        return {
            "raw_flops": result.flops,
            "legs": len(slicing.legs),
            "num_slices": slicing.num_slices,
            "sliced_flops": total,
            "hoisted_flops": hoisted,
            "predicted_seconds": seconds,
            # the slicing-overhead column: sliced work over the plan's
            # own unsliced flops
            "overhead": round(total / max(result.flops, 1.0), 3),
            "seconds": round(plan_s, 3),
        }

    return {
        "cores": len(tn),
        "target_log2": target_log2,
        "post": plan(False),
        "joint": plan(True),
        "fleet_trials": measure_fleet_trials(tn, target),
    }


def measure_fleet_trials(tn, target: float) -> dict:
    """The planner-fleet column: the same deterministic trial grid run
    distributed (2 standalone workers racing claims over one trial
    board) and single-node (in-process), best-by-digest merged each
    way. Trials are pure functions of (structure, spec), so the two
    arms select from the identical candidate set — the gate pins
    distributed <= single (an exact tie in practice; any gap means the
    trial path went nondeterministic or the merge lost results)."""
    import subprocess
    import tempfile

    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.serve.plansvc import (
        TrialBoard,
        best_plan,
        run_trials_local,
        seed_trials,
    )

    leaves = flat_leaf_tensors(tn)
    specs = seed_trials(
        FLEET_NTRIALS, seed=42,
        sa_steps=FLEET_SA_STEPS, sa_rounds=FLEET_SA_ROUNDS,
    )
    t0 = time.perf_counter()
    single = best_plan(run_trials_local(leaves, target, specs))
    single_s = time.perf_counter() - t0

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with tempfile.TemporaryDirectory() as tmp:
        board = TrialBoard(tmp, owner="seed")
        board.publish_structure(leaves, target, key="fleet_trials")
        for spec in specs:
            board.post_trial(spec)
        env = dict(os.environ)
        env.setdefault("TNC_TPU_PLATFORM", "cpu")
        t0 = time.perf_counter()
        workers = [
            subprocess.Popen(
                [sys.executable, "-m", "tnc_tpu.serve.plansvc", tmp,
                 "--owner", f"w{i}"],
                cwd=repo, env=env,
                stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT,
            )
            for i in range(2)
        ]
        for w in workers:
            w.wait(timeout=1200)
        distributed_s = time.perf_counter() - t0
        results = board.results()
        merged = best_plan(results)

    inf = float("inf")
    return {
        "ntrials": FLEET_NTRIALS,
        "results": len(results),
        "single_hoisted_flops": single.cost if single else inf,
        "distributed_hoisted_flops": merged.cost if merged else inf,
        "digest_match": bool(
            merged and single and merged.digest() == single.digest()
        ),
        "single_seconds": round(single_s, 3),
        "distributed_seconds": round(distributed_s, 3),
    }


def measure_sliced_gate_networks() -> dict:
    out = {}
    for name in SLICED_GATE_NETWORKS:
        print(f"measuring sliced gate network {name} ...", flush=True)
        out[name] = measure_sliced_gate_network(name)
    return out


def measure(depth: int, seed: int, ntrials: int, target_log2: float) -> dict:
    """The full north-star measurement (slow: sycamore53 at 128 trials)."""
    from tnc_tpu.builders.sycamore_circuit import sycamore_circuit
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.contractionpath.paths.hyper import Hyperoptimizer
    from tnc_tpu.contractionpath.slicing import (
        slice_and_reconfigure,
        sliced_flops,
    )
    from tnc_tpu.tensornetwork.simplify import simplify_network

    rng = np.random.default_rng(seed)
    raw, _ = sycamore_circuit(53, depth, rng).into_amplitude_network("0" * 53)
    tn = simplify_network(raw)

    t0 = time.perf_counter()
    greedy = Greedy(OptMethod.GREEDY).find_path(tn)
    greedy_s = time.perf_counter() - t0

    target = 2.0**target_log2
    t0 = time.perf_counter()
    hyper = Hyperoptimizer(ntrials=ntrials, seed=seed, target_size=target).find_path(tn)
    hyper_s = time.perf_counter() - t0
    hyper2 = Hyperoptimizer(ntrials=ntrials, seed=seed, target_size=target).find_path(tn)

    # deep circuits can't reach the single-chip target within the slice
    # cap — relax by 4x until feasible (the artifact records the target)
    slice_target = target
    t0 = time.perf_counter()
    while True:
        try:
            pairs, slicing = slice_and_reconfigure(
                list(tn.tensors), hyper.ssa_path.toplevel, slice_target,
                fuse=False,  # a score of the search, in multiply-adds
            )
            break
        except ValueError:
            if slice_target > 2.0**62:
                raise
            slice_target *= 4.0
    slice_s = time.perf_counter() - t0
    total = sliced_flops(list(tn.tensors), ContractionPath.simple(pairs).toplevel, slicing)

    return {
        "tensors": len(raw),
        "cores": len(tn),
        "greedy": {
            "flops": greedy.flops,
            "log2_peak": float(np.log2(max(greedy.size, 1))),
            "seconds": round(greedy_s, 3),
        },
        "hyper": {
            "flops": hyper.flops,
            "log2_peak": float(np.log2(max(hyper.size, 1))),
            "seconds": round(hyper_s, 3),
        },
        "hyper_vs_greedy_flops": round(greedy.flops / max(hyper.flops, 1), 1),
        "deterministic": hyper2.flops == hyper.flops,
        "sliced": {
            "target_log2": float(np.log2(slice_target)),
            "legs": len(slicing.legs),
            "num_slices": slicing.num_slices,
            "total_flops": total,
            "overhead_vs_unsliced": round(total / max(hyper.flops, 1), 3),
            "seconds": round(slice_s, 3),
        },
    }


# ---------------------------------------------------------------------------
# Gate mode


def _allowed_ratio(min_tol: float, max_tol: float) -> float:
    """perf_gate's tolerance discipline applied to deterministic plan
    metrics: no rep spread exists, so the floor is the whole budget —
    but the cap still documents that nothing excuses a blow-up."""
    return 1.0 + min(max(min_tol, 0.0), max_tol)


def compare_quality(
    base: dict,
    fresh: dict,
    min_tol: float = 0.25,
    max_tol: float = 0.60,
    peak_tol_bits: float = 2.0,
) -> tuple[int, list[str]]:
    """Gate logic; returns (exit_code, messages). Pure on dicts so the
    tests drive it without subprocesses.

    Per network, the gated metrics are the planner outputs: greedy
    flops, hyper flops, hyper log2 peak (additive bits tolerance), and
    the calibrated plan's predicted seconds. Improvements always pass;
    within-record, the calibrated plan must not predict worse than the
    flops plan beyond the tolerance (the objective's whole point).

    The ``sliced_gate_networks`` block is gated the same way (joint
    plan hoisted sliced flops + predicted seconds vs baseline) plus two
    within-record invariants on the fresh measurement: the joint
    tree+slice search must not lose to the post-pass pipeline on ANY
    network (beyond float noise), and must beat it strictly on at
    least one — otherwise making slicing a search dimension has
    silently stopped paying.

    The ``fleet_trials`` column inside each sliced entry adds the
    distributed-planning invariant: the fleet fan-out (same trial
    budget, 2 processes) must tie or beat the single-node run on
    hoisted sliced cost — trials are deterministic, so a loss means
    nondeterminism or a dropped result, never "bad luck".
    """
    base_nets = base.get("gate_networks")
    fresh_nets = fresh.get("gate_networks")
    if not isinstance(base_nets, dict) or not base_nets:
        return 2, ["baseline record has no gate_networks block"]
    if not isinstance(fresh_nets, dict) or not fresh_nets:
        return 2, ["fresh record has no gate_networks block"]
    missing = sorted(set(base_nets) - set(fresh_nets))
    if missing:
        # a baseline network the fresh run failed to measure (builder
        # break, rename) must not silently drop out of the gate
        return 2, [
            "fresh record is missing gate network(s): "
            + ", ".join(missing)
        ]
    common = sorted(set(base_nets) & set(fresh_nets))
    if not common:
        return 2, ["no common gate networks between baseline and fresh"]

    allowed = _allowed_ratio(min_tol, max_tol)
    verdict = 0
    msgs: list[str] = []

    def ratio_check(net: str, label: str, b: float, f: float) -> None:
        nonlocal verdict
        if not b or b <= 0.0:
            return
        r = f / b
        msgs.append(
            f"{net}.{label}: baseline {b:.4g} -> fresh {f:.4g} "
            f"(ratio {r:.3f}, allowed {allowed:.3f})"
        )
        if r > allowed:
            verdict = 1
            msgs.append(
                f"PLAN REGRESSION: {net}.{label} is {r:.2f}x the "
                f"committed baseline (allowed {allowed:.2f}x)"
            )

    for net in common:
        b, f = base_nets[net], fresh_nets[net]
        ratio_check(net, "greedy.flops", b["greedy"]["flops"], f["greedy"]["flops"])
        ratio_check(net, "hyper.flops", b["hyper"]["flops"], f["hyper"]["flops"])
        ratio_check(
            net, "calibrated.predicted_seconds",
            b["calibrated"]["predicted_seconds"],
            f["calibrated"]["predicted_seconds"],
        )
        db = f["hyper"]["log2_peak"] - b["hyper"]["log2_peak"]
        if db > peak_tol_bits:
            verdict = 1
            msgs.append(
                f"PLAN REGRESSION: {net}.hyper.log2_peak grew "
                f"{db:.2f} bits (allowed {peak_tol_bits:.2f})"
            )
        # within-record invariant: the seconds-objective plan must not
        # predict worse than the flops-objective plan
        cal = f["calibrated"]["predicted_seconds"]
        flo = f["hyper"]["predicted_seconds"]
        if flo and cal > flo * allowed:
            verdict = 1
            msgs.append(
                f"PLAN REGRESSION: {net} calibrated-objective plan "
                f"predicts {cal:.4g}s vs flops-objective {flo:.4g}s — "
                "the calibrated objective stopped helping"
            )

    # -- sliced gate: joint tree+slice search vs post-pass pipeline --
    base_sl = base.get("sliced_gate_networks")
    fresh_sl = fresh.get("sliced_gate_networks")
    if isinstance(base_sl, dict) and base_sl:
        if not isinstance(fresh_sl, dict) or not fresh_sl:
            return 2, msgs + [
                "fresh record has no sliced_gate_networks block"
            ]
        missing = sorted(set(base_sl) - set(fresh_sl))
        if missing:
            return 2, msgs + [
                "fresh record is missing sliced gate network(s): "
                + ", ".join(missing)
            ]
    if isinstance(fresh_sl, dict) and fresh_sl:
        # a hair of float slack: both pipelines are deterministic, but
        # exact ties must never trip the "joint lost" check
        tie = 1.0 + 1e-9
        strict_win = False
        for net in sorted(fresh_sl):
            f = fresh_sl[net]
            joint, post = f["joint"], f["post"]
            if isinstance(base_sl, dict) and net in base_sl:
                b = base_sl[net]
                ratio_check(
                    net, "joint.hoisted_flops",
                    b["joint"]["hoisted_flops"], joint["hoisted_flops"],
                )
                ratio_check(
                    net, "joint.predicted_seconds",
                    b["joint"]["predicted_seconds"],
                    joint["predicted_seconds"],
                )
                bft = b.get("fleet_trials")
                if isinstance(bft, dict):
                    fft = f.get("fleet_trials")
                    if not isinstance(fft, dict):
                        # the baseline measured distributed planning;
                        # a fresh run that silently dropped the column
                        # must not pass by omission
                        return 2, msgs + [
                            "fresh record is missing the fleet_trials "
                            f"block for {net}"
                        ]
                    ratio_check(
                        net, "fleet_trials.distributed_hoisted_flops",
                        bft["distributed_hoisted_flops"],
                        fft["distributed_hoisted_flops"],
                    )
            # the gated sliced totals are what the hoisting executors
            # actually pay: the hoist-aware flop total and the predicted
            # seconds — the naive num_slices x per-slice total stays a
            # recorded column (a joint plan may trade a hair of naive
            # total for a larger hoistable stem, and that trade is the
            # objective, not a regression)
            for metric in ("hoisted_flops", "predicted_seconds"):
                if joint[metric] > post[metric] * tie:
                    verdict = 1
                    msgs.append(
                        f"PLAN REGRESSION: {net} joint {metric} "
                        f"{joint[metric]:.4g} exceeds the post-pass "
                        f"pipeline's {post[metric]:.4g} — the joint "
                        "search lost to optimize-then-slice"
                    )
                if joint[metric] < post[metric]:
                    strict_win = True
            # fleet invariant: the distributed fan-out selects from the
            # same deterministic candidate set as a single node at the
            # same trial budget — ties allowed, losses never
            ft = f.get("fleet_trials")
            if isinstance(ft, dict):
                dist = ft["distributed_hoisted_flops"]
                single = ft["single_hoisted_flops"]
                if dist > single * tie:
                    verdict = 1
                    msgs.append(
                        f"PLAN REGRESSION: {net} distributed fleet "
                        f"search ({dist:.4g} hoisted flops over 2 "
                        f"procs) lost to single-node ({single:.4g}) at "
                        "the same trial budget — trial determinism "
                        "broke or the merge dropped results"
                    )
        if not strict_win:
            verdict = 1
            msgs.append(
                "PLAN REGRESSION: the joint search beats the post-pass "
                "pipeline on NO sliced gate network — slicing-aware "
                "pathfinding has stopped paying for itself"
            )
    return verdict, msgs


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--depths", nargs="+", type=int, default=[14, 20])
    ap.add_argument("--ntrials", type=int, default=128)
    ap.add_argument("--target-log2", type=float, default=28.0)
    ap.add_argument("--out", default="PLANNER_QUALITY.json")
    ap.add_argument(
        "--fast", action="store_true",
        help="measure only the fast gate_networks set (check.sh / CI)",
    )
    ap.add_argument(
        "--gate", metavar="BASELINE",
        help="compare fresh plan metrics against this committed record; "
             "exit 1 on a plan-cost regression",
    )
    ap.add_argument(
        "--fresh", metavar="RECORD",
        help="(gate mode) use this previously written record instead of "
             "recomputing — lets one measurement drive several gates",
    )
    ap.add_argument("--min-tol", type=float, default=0.25)
    ap.add_argument("--max-tol", type=float, default=0.60)
    args = ap.parse_args()

    if args.gate:
        try:
            with open(args.gate, encoding="utf-8") as fh:
                base = json.load(fh)
        except (OSError, json.JSONDecodeError) as e:
            print(f"planner gate: cannot load baseline: {e}", file=sys.stderr)
            return 2
        if args.fresh:
            try:
                with open(args.fresh, encoding="utf-8") as fh:
                    fresh = json.load(fh)
            except (OSError, json.JSONDecodeError) as e:
                print(
                    f"planner gate: cannot load fresh record: {e}",
                    file=sys.stderr,
                )
                return 2
        else:
            fresh = {"gate_networks": measure_gate_networks()}
        code, msgs = compare_quality(
            base, fresh, min_tol=args.min_tol, max_tol=args.max_tol
        )
        for m in msgs:
            print(
                f"planner gate: {m}", file=sys.stderr if code else sys.stdout
            )
        print(
            "planner gate: FAILED" if code else "planner gate: OK",
            file=sys.stderr if code else sys.stdout,
        )
        return code

    out = {
        "description": (
            "Planner quality: native Hyperoptimizer (128 trials, seed 42) "
            "vs Greedy on the BASELINE north-star networks, "
            "slice-and-reconfigure overhead at the single-chip HBM "
            "target, the fast gate_networks set (greedy / "
            "flops-objective hyper / calibrated-objective hyper, priced "
            "under reference_model), and the sliced_gate_networks set "
            "(budget-constrained: joint tree+slice search vs the classic "
            "hyper-then-slice post-pass, with the slicing-overhead "
            "column) gated in CI by scripts/planner_quality.py --gate. "
            "Regenerate with scripts/planner_quality.py [--fast]."
        ),
        "reference_model": dict(REFERENCE_MODEL),
    }
    if args.fast and os.path.exists(args.out):
        # --fast refreshes only the gate set; carry the existing (slow)
        # north-star entries forward untouched
        with open(args.out, encoding="utf-8") as fh:
            try:
                prev = json.load(fh)
            except json.JSONDecodeError:
                prev = {}
        for key, value in prev.items():
            if key.startswith("sycamore"):
                out[key] = value
    if not args.fast:
        for depth in args.depths:
            key = f"sycamore53_m{depth}"
            print(f"measuring {key} ...", flush=True)
            out[key] = measure(depth, 42, args.ntrials, args.target_log2)
    out["gate_networks"] = measure_gate_networks()
    out["sliced_gate_networks"] = measure_sliced_gate_networks()
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
