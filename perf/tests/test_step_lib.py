"""The per-step readers (``perf/step_lib.py`` and the ``step_*``
metrics): a window's ops written by hand and put through
``trace_reduce.reduce_events``, joined with a program's op table written
by hand; ``None`` from a program without the table and from a stale one;
the declarations in ``BENCHMARK.json``."""

import importlib.util
import json
import os
from types import SimpleNamespace

import pytest

from perf import common, step_lib, trace_reduce

MS = 1e6  # ns
MODULE = "jit_tnc_residual_c00"
NEW = [
    "step_attributed_pct.amp", "step_attributed_pct.serve",
    "step_dot_share_pct.amp", "step_dot_share_pct.serve",
    "step_prep_share_pct.amp", "step_prep_share_pct.serve",
    "step_tiled_ps_per_elem", "step_staged_ps_per_elem",
    "step_matrix_ps_per_elem", "step_worst_ratio",
]


def _metric(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"),
        os.path.join(common.PERF_DIR, "metrics", f"{name}.py"),
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _op(steps, part, owners=None):
    owners = [f"tnc.step.{n:04d}" for n in steps] if owners is None else owners
    return {"steps": steps, "owners": owners, "part": part, "opcode": "fusion"}


def _table(status="ok"):
    """Five steps of a chunk program: three large block steps (tiled,
    staged, tiled), one large gauss step (matrix), one small step; each
    streams 1e6 elements a slice but the last."""
    rows = ((0, "large", "block", "tiled", 1e6), (1, "large", "block", "staged", 1e6),
            (2, "large", "block", "tiled", 1e6), (3, "large", "gauss", "matrix", 1e6),
            (4, "small", "block", "matrix", 1e3))
    facts = [
        {"number": n, "scope": f"tnc.step.{n:04d}.{size}.{mode}.{form}", "size": size,
         "mode": mode, "form": form, "elements": elements, "macs": 1.0, "k": 2,
         "runs": "row", "plan_index": 100 + n}
        for n, size, mode, form, elements in rows
    ]
    ops = {
        "copy.1": _op([0], "prep"), "fusion.1": _op([0], "dot"),
        "copy.2": _op([1], "prep"), "fusion.2": _op([1], "dot"),
        "fusion.3": _op([2], "dot"), "fusion.4": _op([3], "dot"),
        "fusion.5": _op([4], "out"), "fusion.6": _op([1, 2], "out"),
        "add.7": _op([], "slice.sum", ["tnc.slice.sum"]),
        "while.8": _op([], None, []),
    }
    return {MODULE: [{"status": status, "why": "old scopes" if status != "ok" else "",
                      "module": MODULE, "seconds": 0.25, "steps": facts, "ops": ops}]}


def _run(table, chips=1, units=10):
    """A window of 100 ms on ``chips`` chips, the ops one after another:
    per chip 10+20 ms (step 0), 10+30 (step 1), 10 (step 2), 10 (step 3),
    1 (step 4), 4 mixed (steps 1 and 2), 2 the slice sum, 1 under no
    scope, 2 of a module the program does not know."""
    at, ops = 0.0, []
    for name, ms in (("copy.1", 10), ("fusion.1", 20), ("copy.2", 10), ("fusion.2", 30),
                     ("fusion.3", 10), ("fusion.4", 10), ("fusion.5", 1), ("fusion.6", 4),
                     ("add.7", 2), ("broadcast.9", 1)):
        ops.append((f"%{name} = f32[8] fusion(x)", at * MS, (at + ms) * MS))
        at += ms
    dev = {"modules": [(f"{MODULE}(7)", 0.0, 98 * MS), ("jit_other(3)", 98 * MS, 100 * MS)],
           "ops": ops + [("%copy.1 = f32[8] copy(x)", 98 * MS, 100 * MS)]}
    spans = [("perf:window", 0.0, 100 * MS), ("perf:call", 0.0, 100 * MS)]
    reduced = trace_reduce.reduce_events({n: dev for n in range(chips)}, spans)
    run = SimpleNamespace(reduced=reduced, window={"units": units}, chips=chips, state={})
    run.state["table"] = table
    return run


@pytest.fixture(autouse=True)
def program_table(monkeypatch):
    """The program's side, by hand: the table a test put in its run."""
    holder = {}

    def fake(modules):
        from tnc_tpu import obs

        holder["asked"] = set(modules)
        return holder["run"].state["table"], obs.step_seconds

    monkeypatch.setattr(step_lib, "_program_table", fake)
    return holder


def _read(holder, run, name):
    holder["run"] = run
    return _metric(name).read(run)


def test_every_new_metric_on_a_window_written_by_hand(program_table, capsys):
    run = _run(_table())
    read = lambda name: _read(program_table, run, name)  # noqa: E731
    # 100 ms of ops: 94 under one owner, 4 mixed, 1 + 2 under none
    assert read("step_attributed_pct.amp") == pytest.approx(93.0)
    assert read("step_attributed_pct.serve") == pytest.approx(93.0)
    # dots 20 + 30 + 10 + 10 of the 93 attributed; prep 20 and out 1
    assert read("step_dot_share_pct.amp") == pytest.approx(100 * 70 / 93)
    assert read("step_dot_share_pct.serve") == pytest.approx(100 * 70 / 93)
    assert read("step_prep_share_pct.amp") == pytest.approx(100 * 21 / 93)
    assert read("step_prep_share_pct.serve") == pytest.approx(100 * 21 / 93)
    # tiled: steps 0 and 2, 30 + (10 + 2 of the mixed op) ms over 10
    # slices x 2e6 elements; staged: step 1, 40 + 2; matrix: the gauss step
    assert read("step_tiled_ps_per_elem") == pytest.approx(0.042 * 1e12 / 2e7)
    assert read("step_staged_ps_per_elem") == pytest.approx(0.042 * 1e12 / 1e7)
    assert read("step_matrix_ps_per_elem") == pytest.approx(0.010 * 1e12 / 1e7)
    # block steps: 3000, 4200, 1200 ps an element; the small one is out
    assert read("step_worst_ratio") == pytest.approx(4200 / 3000)
    # the program was asked once, for the modules of the window, and one
    # phase line was printed
    assert program_table["asked"] == {MODULE, "jit_other"}
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    (steps,) = [l for l in lines if l.get("phase") == "steps"]
    assert steps["by_part"] == pytest.approx({"prep": 0.020, "dot": 0.070, "out": 0.001,
                                              "slice.sum": 0.002})
    assert steps["by_form"] == pytest.approx({"tiled": 0.040, "staged": 0.040, "matrix": 0.011})
    assert steps["by_mode"] == pytest.approx({"block": 0.081, "gauss": 0.010})
    assert steps["mixed_s"] == pytest.approx(0.004)
    assert steps["unattributed_s"] == pytest.approx(0.003)
    assert steps["unknown_ops"] == 1  # broadcast.9: no key of the table
    first = steps["costliest_steps"][0]
    assert first["scope"] == "tnc.step.0001.large.block.staged"
    assert first["plan_index"] == 101 and first["elements"] == 1e6
    assert first["ms_per_unit"] == pytest.approx(4.2)
    assert first["ps_per_elem"] == pytest.approx(4200.0)
    assert first["by_part_ms"] == pytest.approx({"prep": 1.0, "dot": 3.0})
    assert len(steps["costliest_steps"]) == 5


def test_four_chips_share_the_windows_units(program_table):
    # the reduction averages an op's seconds over the chips, and a chip
    # ran a quarter of the window's slices
    run = _run(_table(), chips=4, units=40)
    assert _read(program_table, run, "step_staged_ps_per_elem") == pytest.approx(4200.0)
    assert _read(program_table, run, "step_attributed_pct.amp") == pytest.approx(93.0)


@pytest.mark.parametrize("name", NEW)
def test_no_table_and_a_stale_table_read_none(program_table, monkeypatch, name, capsys):
    stale = _run(_table("stale"))
    assert _read(program_table, stale, name) is None
    (line,) = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert line["phase"] == "steps" and line["stale"] == {MODULE: "old scopes"}
    untraced = SimpleNamespace(reduced=None, window={"units": 10}, chips=1, state={})
    assert _metric(name).read(untraced) is None
    # the parent of the PR that added the table: no such function
    monkeypatch.setattr(step_lib, "_program_table", lambda modules: None)
    parent = _run(None)
    assert _metric(name).read(parent) is None
    assert capsys.readouterr().out == ""


def test_a_program_without_the_table_is_what_the_real_look_up_finds(monkeypatch):
    import tnc_tpu.obs as obs

    monkeypatch.undo()  # the real look-up, not the fixture's
    monkeypatch.delattr(obs, "device_op_table")
    assert step_lib._program_table({"jit_x"}) is None


def test_a_failing_look_up_says_so_and_reads_none(program_table, monkeypatch, capsys):
    def broken(modules):
        raise RuntimeError("compile failed")

    monkeypatch.setattr(step_lib, "_program_table", broken)
    run = _run(_table())
    assert _metric("step_attributed_pct.amp").read(run) is None
    (line,) = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert line["phase"] == "steps" and "compile failed" in line["error"]


@pytest.mark.parametrize("name", NEW)
def test_new_benchmark_entries_name_files_and_cells_that_exist(name):
    with open(os.path.join(common.CHECKOUT, "BENCHMARK.json"), encoding="utf-8") as fh:
        bench = json.load(fh)
    (entry,) = [e for e in bench["per_layer"] if e["name"] == name]
    module = _metric(name)
    assert (module.name, module.unit, module.layer, module.moves, module.workloads) == (
        entry["name"], entry["unit"], "kernels", entry["moves"], entry["workloads"])
    assert entry["source"] == "device_trace" and entry["layer"] == "kernels"
    cells = {c["name"] for c in bench["workloads"]}
    assert entry["workloads"] and set(entry["workloads"]) <= cells
    moved = next(e for e in bench["end_to_end"] if e["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved["workloads"])
    served = name.endswith(".serve")
    assert (entry["moves"] == "amps_per_s") == served
    assert entry["better"] == ("higher" if "attributed" in name or "dot_share" in name else "lower")
