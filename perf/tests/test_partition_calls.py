"""The partition-parallel cell checked against itself, on the CPU (four
virtual devices): a rehearsal of ``partition_calls`` through
``perf.run.drive``, the faults and the lower-precision control that have
to come out as not correct, the plan the cell states (the same on this
host as on the chip's, for every seed), the roofline's bytes for
intermediates over the on-chip limit by hand, and the four new metric
readers on events written by hand."""

import copy
import types

import pytest

from perf import chip_lib, circuits, common, compare, reference, roofline, trace_reduce
from perf import run as perf_run
from perf.traffic import partition_calls

CELL = "sycamore30_m14_part4.fanin_calls"
PEAKS = common.load_json("peaks.json")["TPU v5 lite"]
REAL = common.load_json("configs", "sycamore30_m14_part4.json")
TINY = {**REAL, "circuit": {"family": "sycamore", "qubits": 14, "cycles": 8},
        "reference_order": {**REAL["reference_order"], "ntrials": 2, "polish_rounds": 1}}
WORKLOAD = {**common.load_json("workloads", f"{CELL}.json"), "limits": {"amp_gap": 1e-5}}
BENCH = perf_run.load_benchmark()


def make_run(seed=11, seconds=0.3, config=TINY):
    import jax

    return perf_run.Run(
        workload=copy.deepcopy(WORKLOAD), config=copy.deepcopy(config),
        cell=perf_run.find_cell(BENCH, CELL), seed=seed, seconds=seconds, trace=False,
        chips=4, device=common.device_record(jax, 4), peaks=PEAKS,
        compiles=common.CompileCounter().install(),
    )


def rehearse(**kw):
    run = make_run(**kw)
    return perf_run.drive(run, BENCH), run


# -- rehearsal, faults, control ------------------------------------------


def test_rehearsal_partition_calls():
    # a seed over 2**31, as the driver's are
    result, run = rehearse(seed=3000000019)
    assert result["correct"] is True, result["numbers"]
    assert result["device"]["count"] == 4
    assert result["failed"] == 0 and result["attempted"] == run.window["units"] >= 1
    assert set(result["metrics"]) == {"amplitude_s", "setup_s"}
    w = run.window
    # a call is a whole amplitude: nothing is scaled
    assert result["metrics"]["amplitude_s"]["value"] == pytest.approx(w["window_s"] / len(w["calls"]))
    assert result["numbers"]["amp_gap"]["value"] < 1e-5
    assert result["numbers"]["programs_built_in_window"] == {"value": 0, "limit": 0}
    # the program's always-on counts of a call
    counters = run.state["counters"]
    assert counters["fanin.pairs"] == 3 and 1 <= counters["fanin.levels"] <= 3
    assert counters["fanin.bytes"] > 0 and counters["fetch.bytes"] == 8
    assert counters["local.cmacs_max"] >= counters["local.cmacs_mean"] > 0
    # the order that ran and the order the reference got are two orders of
    # the same leaves; every leaf varies, so the roofline counts every call
    q = run.state["question"]
    assert len(q["pairs"]) == len(q["leaf_legs"]) - 1
    assert q["varying_leaves"] == tuple(range(len(q["leaf_legs"])))
    # what the unlisted amplitude metrics read is filled: none is None
    assert run.setup["sliced_cmacs"] > 0 and run.setup["plan_s"] > 0
    for name in ("plan_sliced_cmacs", "call_ms_per_slice_p50", "window_mfu.amp", "plan_s",
                 "first_call_s"):
        assert perf_run.load_metric(name).read(run) is not None, name
    # another seed: other gates, the same structure and the same plan
    other = make_run(seed=12)
    partition_calls.prepare(other)
    assert other.setup["structure_digest"] == run.setup["structure_digest"]
    assert other.setup["plan_digest"] == run.setup["plan_digest"]


def test_fault_partition_result_zeroed_is_not_correct(monkeypatch):
    import jax

    from tnc_tpu.parallel import partitioned

    real = partitioned.local_contract_partitions

    def zeroed(*args, **kw):
        results = real(*args, **kw)
        results[1] = jax.tree.map(lambda x: x * 0, results[1])
        return results

    monkeypatch.setattr(partitioned, "local_contract_partitions", zeroed)
    result, _ = rehearse(seed=13, seconds=0.05)
    assert result["correct"] is False
    assert result["numbers"]["amp_gap"]["value"] > 0.05


def test_fault_fanin_pair_skipped_is_not_correct(monkeypatch):
    from tnc_tpu.parallel import partitioned

    real = partitioned.intermediate_reduce

    def skipping(comm, toplevel, results, split_complex, precision, levels=None):
        return real(comm, list(toplevel)[:-1], results, split_complex, precision)

    # the shortened path leaves two tensors: hand back the chain's
    monkeypatch.setattr(partitioned, "_fanin_survivor",
                        lambda k, toplevel: toplevel[-1][0] if toplevel else 0)
    monkeypatch.setattr(partitioned, "intermediate_reduce", skipping)
    result, _ = rehearse(seed=13, seconds=0.05)
    assert result["correct"] is False
    assert result["numbers"]["amp_gap"]["value"] > 0.05


def test_control_lower_precision_is_not_correct():
    """The reference in three bfloat16 passes (a TPU's 'high'), put in the
    program's place, reads above the limit the program's own answers pass.
    (On the chip the control is the program itself under its own
    TNC_TPU_DOT_PRECISION=high: PERF.md; on the CPU that path is inert.)"""
    n = TINY["circuit"]["qubits"]
    for seed in (21, 22, 23):
        run = make_run(seed=seed)
        partition_calls.prepare(run)
        question = partition_calls._reference_question(run)
        gates, bits = run.state["gates"], run.state["bits"]
        want = compare.amplitudes(gates, n, question, [bits])[0]
        low = compare.amplitudes(gates, n, question, [bits], "bf16x3")[0]
        gap = abs(low - want) / max(abs(want), 2.0 ** (-n / 2))
        print("control gap", seed, gap)
        assert gap > WORKLOAD["limits"]["amp_gap"], gap


# -- the plan the configuration states -----------------------------------


def test_the_cells_plan_is_the_chips_for_every_seed():
    """n = 30, m = 14: partitioner and Greedy are milliseconds, nothing is
    contracted. The digest is the one the chip's host printed (my chip
    runs, PR 28): the sandbox's host and the chip's find the same plan."""
    infos = []
    for seed in (1, 3000000061):
        run = make_run(seed=seed, config=REAL)
        infos.append(partition_calls._plan(run)["info"])
    a, b = infos
    assert a["plan_digest"] == b["plan_digest"] == "5e1c1bff4cea5cf1"
    assert a["structure_digest"] == b["structure_digest"] == "58015acfaafc695f"
    assert a["partition_leaves"] == [35, 33, 35, 34]
    assert a["partition_result_log2"] == [25.0, 25.0, 22.0, 28.0]
    assert a["fanin"] == [[0, 3], [0, 1], [0, 2]]
    assert a["fanin_moved_bytes"] == 8 * (2**28 + 2**25 + 2**22)
    assert a["plan_cmacs"] == 622685131520.0  # 2^39.18


def test_real_plan_roofline_count():
    """What ``contraction_roofline.amp`` and ``window_mfu.amp`` count for
    one call of the real plan: 8 real operations a multiply-add of the
    order that RAN; bytes for the three fan-in operands and results over
    128 MiB; operations bind it. ``metric_lib.roofline_pct`` gives one chip
    a QUARTER of the window's calls and divides by the busiest chip's op
    seconds; the survivor's chip runs 99 % of the operations, so the share
    reads a quarter of that chip's own and cannot pass 100 %."""
    run = make_run(seed=1, config=REAL)
    plan = partition_calls._plan(run)
    q = plan["question"]
    shapes = reference.plan_shapes(q["leaf_legs"], q["pairs"], q["leg_dims"],
                                   q["sliced_legs"], q["varying_leaves"])
    assert all(s["varies"] for s in shapes)
    one = roofline.window_cost(shapes, 1, PEAKS["on_chip_vector_bytes"])
    two = roofline.window_cost(shapes, 2, PEAKS["on_chip_vector_bytes"])
    assert one["ops"] == 8 * plan["info"]["plan_cmacs"]
    assert two == {"ops": 2 * one["ops"], "bytes": 2 * one["bytes"]}
    # pair (0,3): 2^25 and 2^28 in, 2^25 out; pair (0,1): 2^25 and 2^25 in
    # (2^22 out is 32 MiB: not counted); each operand was written by the
    # step before it and is read here: these alone are 8 B x ...
    fanin = 8 * (2 * 2**28 + 2 * 2**25 + 2 * 2**25 + 2 * 2**25)
    assert fanin < one["bytes"] < 2 * fanin  # the rest: local steps over 128 MiB, leaves
    least = roofline.least_seconds(one, PEAKS)
    assert least["bound"] == "operations"
    assert least["seconds"] == pytest.approx(one["ops"] / 197e12)


def test_roofline_bytes_over_the_on_chip_limit_by_hand():
    # L0[i,k] 2^25 elements, L1[k,x] 2^21, L2[x,j] 2^21; L1*L2 -> M[k,j] of
    # 2^28 elements (2 GiB: written once, read once), L0*M -> R[i,j] of 2^25
    dims = {"i": 2**11, "k": 2**14, "x": 2**7, "j": 2**14}
    leaf_legs = [("i", "k"), ("k", "x"), ("x", "j")]
    shapes = reference.plan_shapes(leaf_legs, [(1, 2), (0, 1)], dims, (), (0, 1, 2))
    assert [(s["k"], s["m"], s["n"]) for s in shapes] == [
        (2**7, 2**14, 2**14), (2**14, 2**11, 2**14)]
    cost = roofline.window_cost(shapes, 3, PEAKS["on_chip_vector_bytes"])
    leaves = 8 * (2**25 + 2**21 + 2**21)
    assert cost["bytes"] == 3 * (leaves + 2 * 8 * 2**28 + 8 * 2**25)
    assert cost["ops"] == 3 * 8 * (2**7 * 2**28 + 2**14 * 2**25)
    # with a 4 GiB on-chip memory M would not count
    fused = roofline.window_cost(shapes, 3, 2**32)
    assert fused["bytes"] == 3 * (leaves + 8 * 2**25)


# -- the new metric readers on events written by hand ---------------------

MS = 1e6  # ns


def _traced(local="jit_tnc_partition_local", pair="jit_tnc_fanin_pair", prefix="perf:tnc."):
    """A window of 100 ms, one call: scatter 0-10 (all chips idle), the
    local programs from 10 (chip 0: 10 ms, chip 1: 20, chip 2: 2, chip 3:
    28), the host in fanin_level 12-14 and in fetch 14-100, the pair
    program on chip 0 from 50 to 90 (it waited for chip 3 and the move)."""
    spans = [("perf:window", 0.0, 100 * MS), ("perf:call", 0.0, 100 * MS),
             (prefix + "partitioned.scatter", 0.0, 10 * MS),
             (prefix + "partitioned.fanin_level", 12 * MS, 14 * MS),
             (prefix + "partitioned.fetch", 14 * MS, 100 * MS)]

    def chip(local_ms, pair_ms=None):
        mods = [(f"{local}(1)", 10 * MS, (10 + local_ms) * MS)]
        ops = [("%fusion.1 = f32[8] fusion(x)", 10 * MS, (10 + local_ms) * MS)]
        if pair_ms:
            mods.append((f"{pair}(2)", 50 * MS, (50 + pair_ms) * MS))
            ops.append(("%fusion.6 = f32[8] fusion(y)", 50 * MS, (50 + pair_ms) * MS))
        return {"modules": mods, "ops": ops}

    devices = {0: chip(10, 40), 1: chip(20), 2: chip(2), 3: chip(28)}
    run = types.SimpleNamespace(
        reduced=trace_reduce.reduce_events(devices, spans, chips=4),
        window={"per_chip": chip_lib.per_chip(devices, spans, 4)},
    )
    return run


def _read(name, run):
    return perf_run.load_metric(name).read(run)


def test_new_metric_readers_by_hand():
    run = _traced()
    # pair ops 40 ms of 40 + 10 + 20 + 2 + 28
    assert _read("fanin_device_share_pct", run) == pytest.approx(100 * 40 / 100)
    # local op seconds 10, 20, 2, 28: mean 15 of max 28
    assert _read("partition_imbalance_pct", run) == pytest.approx(100 * (1 - 15 / 28))
    # the survivor's chip (0: it ran the pair) idle under fanin_level 12-14:
    # busy 10-20, so 0; under fetch 14-100: idle 20-50 and 90-100 = 40 ms
    assert _read("fanin_move_wait_pct", run) == pytest.approx(40.0)
    # the idlest chip (2) is idle all through scatter's 10 ms
    assert _read("partition_scatter_wait_pct", run) == pytest.approx(10.0)
    survivor = chip_lib.survivor(run.window["per_chip"])
    assert survivor["ordinal"] == 0
    assert survivor["op_s"] == pytest.approx(
        {"jit_tnc_partition_local": 0.010, "jit_tnc_fanin_pair": 0.040})


def test_new_metric_readers_say_nothing_without_their_sources():
    # the parent's program: its partition programs are all jit_tnc_program
    parent = _traced(local="jit_tnc_program", pair="jit_tnc_program")
    for name in ("fanin_device_share_pct", "partition_imbalance_pct", "fanin_move_wait_pct"):
        assert _read(name, parent) is None, name
    assert _read("partition_scatter_wait_pct", parent) == pytest.approx(10.0)  # that span it has
    # a program that writes no span of its own
    spanless = _traced(prefix="perf:other.")
    assert _read("partition_scatter_wait_pct", spanless) is None
    assert _read("fanin_move_wait_pct", spanless) is None
    assert _read("fanin_device_share_pct", spanless) == pytest.approx(40.0)
    # an untraced run
    untraced = types.SimpleNamespace(reduced=None, window={})
    for name in ("fanin_device_share_pct", "partition_imbalance_pct", "fanin_move_wait_pct",
                 "partition_scatter_wait_pct"):
        assert _read(name, untraced) is None, name
    assert chip_lib.read_window("no.such.cell", 4) is None
