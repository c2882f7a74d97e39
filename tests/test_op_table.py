"""The program's op table (``tnc_tpu/obs/op_table.py``): the parser of
optimized HLO text on fixture texts, the join with a window's device
ops on lists written by hand, and what a trace leaves behind."""

import pytest

import tnc_tpu.obs as obs
from tnc_tpu.obs import op_table

S3 = "tnc.step.0003.large.block.tiled"
S4 = "tnc.step.0004.large.block.staged"
S7 = "tnc.step.0007.large.gauss.matrix"


def _meta(path):
    return f'metadata={{op_name="jit(tnc_residual_c00)/{path}" source_file="x.py" source_line=1}}'


def _module(body, fused=""):
    """An optimized module in the TPU compiler's print form: layouts with
    tiles in the types, fused computations first, the entry last."""
    return (
        "HloModule jit_tnc_residual_c00, is_scheduled=true, "
        "entry_computation_layout={(f32[8,128]{1,0:T(8,128)})->f32[8,128]{1,0:T(8,128)}}\n\n"
        + fused
        + "ENTRY %main.9 (Arg_0.1: f32[8,128]) -> f32[8,128] {\n"
        "  %Arg_0.1 = f32[8,128]{1,0:T(8,128)} parameter(0)\n"
        + body
        + "}\n"
    )


def _fused(name, lines):
    return (
        f"%{name} (param_0: f32[8,128]) -> f32[8,128] {{\n"
        "  %param_0 = f32[8,128]{1,0:T(8,128)} parameter(0)\n" + lines + "}\n\n"
    )


CASES = {
    "plain op": (
        _module(
            "  ROOT %copy.5 = f32[8,128]{0,1:T(8,128)} copy(%Arg_0.1), "
            + _meta(f"{S3}/prep/transpose") + "\n"
        ),
        "copy.5", {"steps": [3], "owners": [S3], "part": "prep", "opcode": "copy"},
    ),
    "fusion of one step": (
        _module(
            "  ROOT %fusion.1 = f32[8,128]{1,0:T(8,128)} fusion(%Arg_0.1), "
            "kind=kLoop, calls=%fused_computation.1, "
            + _meta(f"{S3}/out/reshape") + "\n",
            _fused("fused_computation.1",
                   "  %bitcast.1 = f32[128,8]{1,0} bitcast(%param_0), "
                   + _meta(f"{S4}/out/reshape") + "\n"
                   "  %transpose.2 = f32[8,128]{1,0} transpose(%bitcast.1), dimensions={1,0}, "
                   + _meta(f"{S3}/prep/transpose") + "\n"
                   "  ROOT %negate.3 = f32[8,128]{1,0} negate(%transpose.2), "
                   + _meta(f"{S3}/prep/neg") + "\n"),
        ),
        # the bitcast's scope (another step's reshape) does no work
        "fusion.1", {"steps": [3], "owners": [S3], "part": "prep", "opcode": "fusion"},
    ),
    "fusion of two steps": (
        _module(
            "  ROOT %fusion.2 = f32[8,128]{1,0:T(8,128)} fusion(%Arg_0.1), "
            "kind=kLoop, calls=%fused_computation.2\n",
            _fused("fused_computation.2",
                   "  %transpose.2 = f32[8,128]{1,0} transpose(%param_0), dimensions={1,0}, "
                   + _meta(f"{S3}/out/transpose") + "\n"
                   "  ROOT %copy.3 = f32[8,128]{0,1} copy(%transpose.2), "
                   + _meta(f"{S4}/prep/transpose") + "\n"),
        ),
        "fusion.2", {"steps": [3, 4], "owners": [S3, S4], "part": "prep", "opcode": "fusion"},
    ),
    "fusion with a dot": (
        _module(
            "  ROOT %convolution_add_fusion.3 = f32[8,128]{1,0:T(8,128)} fusion(%Arg_0.1), "
            "kind=kOutput, calls=%fused_computation.3, "
            + _meta(f"{S7}/dot/add") + "\n",
            _fused("fused_computation.3",
                   "  %transpose.1 = f32[8,128]{1,0} transpose(%param_0), dimensions={1,0}, "
                   + _meta(f"{S4}/out/transpose") + "\n"
                   "  %convolution.2 = f32[8,128]{1,0} convolution(%transpose.1, %param_0), "
                   "window={size=1}, dim_labels=bf_io->bf, "
                   + _meta(f"{S7}/dot/dot_general") + "\n"
                   "  ROOT %add.3 = f32[8,128]{1,0} add(%convolution.2, %param_0), "
                   + _meta(f"{S7}/out/add") + "\n"),
        ),
        # the dot's step owns it whatever rides along, whatever its root
        "convolution_add_fusion.3",
        {"steps": [7], "owners": [S7], "part": "dot", "opcode": "fusion"},
    ),
    "lanemix matmul of a staged prep": (
        _module(
            "  ROOT %fusion.903 = f32[8,128]{1,0:T(8,128)} fusion(%Arg_0.1), "
            "kind=kOutput, calls=%fused_computation.8, "
            + _meta(f"{S4}/prep/dot_general") + "\n",
            _fused("fused_computation.8",
                   "  ROOT %convolution.5 = f32[8,128]{1,0} convolution(%param_0, %param_0), "
                   "window={size=1}, dim_labels=bf_io->bf, "
                   + _meta(f"{S4}/prep/dot_general") + "\n"),
        ),
        # a dot under `prep` is the lane permutation's one-hot matmul,
        # not the step's contraction
        "fusion.903", {"steps": [4], "owners": [S4], "part": "prep", "opcode": "fusion"},
    ),
    "while body": (
        "HloModule jit_tnc_residual_c00\n\n"
        "%body.4 (p: (s32[], f32[8,128])) -> (s32[], f32[8,128]) {\n"
        "  %p = (s32[], f32[8,128]{1,0:T(8,128)}) parameter(0)\n"
        "  %gte.1 = f32[8,128]{1,0:T(8,128)} get-tuple-element(%p), index=1\n"
        "  %copy.7 = f32[8,128]{0,1:T(8,128)} copy(%gte.1), "
        + _meta(f"tnc.chunk.io/while/body/closed_call/{S4}/prep/transpose") + "\n"
        "  %dynamic-update-slice.8 = f32[8,128]{1,0} dynamic-update-slice(%copy.7, %gte.1), "
        + _meta("tnc.chunk.io/while/body/dynamic_update_slice") + "\n"
        "  ROOT %tuple.9 = (s32[], f32[8,128]) tuple(%gte.1, %dynamic-update-slice.8)\n"
        "}\n\n"
        "%cond.5 (p.1: (s32[], f32[8,128])) -> pred[] {\n"
        "  %p.1 = (s32[], f32[8,128]{1,0:T(8,128)}) parameter(0)\n"
        "  ROOT %lt.2 = pred[] constant(true)\n"
        "}\n\n"
        "ENTRY %main.9 (Arg_0.1: f32[8,128]) -> f32[8,128] {\n"
        "  %Arg_0.1 = f32[8,128]{1,0:T(8,128)} parameter(0)\n"
        "  %while.3 = (s32[], /*index=1*/f32[8,128]{1,0:T(8,128)}) while(%Arg_0.1), "
        "condition=%cond.5, body=%body.4, " + _meta("tnc.chunk.io/while") + "\n"
        "  ROOT %gte.9 = f32[8,128]{1,0:T(8,128)} get-tuple-element(%while.3), index=1\n"
        "}\n",
        # the loop's ops are ops of their own; the innermost scope names one
        "copy.7", {"steps": [4], "owners": [S4], "part": "prep", "opcode": "copy"},
    ),
    "vmap path": (
        _module(
            "  ROOT %copy.6 = f32[8,128]{0,1:T(8,128)} copy(%Arg_0.1), "
            + _meta(f"jit(main)/vmap({S3})/vmap(out)/transpose") + "\n"
        ),
        "copy.6", {"steps": [3], "owners": [S3], "part": "out", "opcode": "copy"},
    ),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_parser_reads_an_ops_owner_from_the_optimized_text(case):
    text, op, want = CASES[case]
    parsed = op_table.parse_hlo_ops(text)
    assert parsed["module"] == "jit_tnc_residual_c00"
    assert parsed["ops"][op] == want
    assert parsed["scopes"] <= {S3, S4, S7}


def test_parser_keeps_the_loops_own_ops_under_the_non_step_scope():
    parsed = op_table.parse_hlo_ops(CASES["while body"][0])
    stack = parsed["ops"]["dynamic-update-slice.8"]
    assert (stack["owners"], stack["part"], stack["steps"]) == (
        ["tnc.chunk.io"], "chunk.io", [])
    # every instruction that runs as an op of its own is a key: the
    # body's and the condition's beside the entry's
    assert {"while.3", "gte.9", "copy.7", "tuple.9", "lt.2"} <= set(parsed["ops"])
    assert parsed["ops"]["tuple.9"]["owners"] == []


def test_parser_gives_the_second_half_of_an_async_pair_to_the_first():
    text = _module(
        "  %copy-start.1 = (f32[8,128], f32[8,128], u32[]) copy-start(%Arg_0.1), "
        + _meta(f"{S3}/prep/transpose") + "\n"
        "  ROOT %copy-done.1 = f32[8,128]{1,0:T(8,128)} copy-done(%copy-start.1)\n"
    )
    ops = op_table.parse_hlo_ops(text)["ops"]
    assert ops["copy-done.1"]["steps"] == [3] and ops["copy-done.1"]["part"] == "prep"


class _Record:
    """What `module_table` reads of a registered program."""

    def __init__(self, scopes, steps=((), (), (), ())):
        self.scopes = dict(scopes)
        self.steps = steps


def test_a_text_with_the_old_bucket_scopes_is_stale(monkeypatch):
    monkeypatch.setattr(op_table, "step_facts", lambda record: [])
    old = _module(
        "  ROOT %copy.5 = f32[8,128]{0,1:T(8,128)} copy(%Arg_0.1), "
        + _meta("tnc.small/transpose") + "\n"
    )
    variant = op_table.module_table(_Record({3: S3}), old)
    assert variant["status"] == "stale" and "no tnc.step." in variant["why"]
    # scopes of an earlier naming of the same steps: not the program's own
    renamed = CASES["plain op"][0].replace(S3, "tnc.step.0003.large.block.matrix")
    variant = op_table.module_table(_Record({3: S3}), renamed)
    assert variant["status"] == "stale" and "not the program's own" in variant["why"]
    assert op_table.module_table(_Record({3: S3}), CASES["plain op"][0])["status"] == "ok"
    # a stale module of the window: the join is not believed
    table = {"jit_tnc_residual_c00": [variant | {"status": "stale"}]}
    assert obs.step_seconds([("jit_tnc_residual_c00/copy.5", 1.0)], table) is None


def _table():
    def op(steps, part, owners=None):
        owners = owners or [f"tnc.step.{n:04d}.x" for n in steps]
        return {"steps": steps, "owners": owners, "part": part, "opcode": "fusion"}

    facts = [
        {"number": n, "scope": f"tnc.step.{n:04d}", "size": "large", "mode": mode,
         "form": form, "elements": 1000.0, "macs": 1.0, "k": 2, "runs": "row",
         "plan_index": 10 + n}
        for n, mode, form in ((0, "block", "tiled"), (1, "block", "staged"),
                              (2, "gauss", "matrix"))
    ]
    return {"jit_tnc_residual_c00": [{
        "status": "ok", "why": "", "module": "jit_tnc_residual_c00", "seconds": 0.5,
        "steps": facts,
        "ops": {
            "copy.1": op([0], "prep"), "fusion.2": op([0], "dot"),
            "copy.3": op([1], "prep"), "fusion.4": op([1], "dot"),
            "fusion.5": op([0, 1], "out"), "fusion.6": op([2], "dot"),
            "fusion.7": op([], "slice.sum", ["tnc.slice.sum"]),
            "while.8": op([], None, []),
        },
    }]}


def test_step_seconds_joins_a_windows_ops_with_the_table():
    ops = [(f"jit_tnc_residual_c00/{op}", s) for op, s in (
        ("copy.1", 2.0), ("fusion.2", 3.0), ("copy.3", 1.0), ("fusion.4", 4.0),
        ("fusion.5", 2.0), ("fusion.6", 5.0), ("fusion.7", 1.0), ("while.8", 0.5),
        ("fusion.99", 0.5),
    )] + [("jit_convert_element_type/copy.1", 1.0)]
    joined = obs.step_seconds(ops, _table())
    assert joined["total_s"] == pytest.approx(20.0)
    assert joined["attributed_s"] == pytest.approx(16.0)
    assert joined["mixed_s"] == pytest.approx(2.0)
    # no owner, an op the table lacks, a module it lacks
    assert joined["unattributed_s"] == pytest.approx(2.0)
    assert joined["unknown_ops"] == ["jit_tnc_residual_c00/fusion.99"]
    assert joined["unattributed"][0] == ("jit_convert_element_type/copy.1", 1.0)
    shares = [100 * joined[k] / joined["total_s"]
              for k in ("attributed_s", "mixed_s", "unattributed_s")]
    assert sum(shares) == pytest.approx(100.0)
    assert joined["by_part"] == pytest.approx(
        {"prep": 3.0, "dot": 12.0, "slice.sum": 1.0})
    assert sum(joined["by_part"].values()) == pytest.approx(joined["attributed_s"])
    assert joined["by_form"] == pytest.approx(
        {"tiled": 5.0, "staged": 5.0, "matrix": 5.0})
    assert joined["by_mode"] == pytest.approx({"block": 10.0, "gauss": 5.0})
    rows = joined["steps"]
    first = rows[("jit_tnc_residual_c00", 0)]
    # the mixed op's seconds are shared equally between its two steps
    assert (first["seconds"], first["mixed_s"]) == pytest.approx((5.0, 1.0))
    assert rows[("jit_tnc_residual_c00", 1)]["mixed_s"] == pytest.approx(1.0)
    assert first["by_part"] == pytest.approx({"prep": 2.0, "dot": 3.0})
    assert (first["plan_index"], first["form"], first["elements"]) == (10, "tiled", 1000.0)
    assert joined["table_s"] == pytest.approx(0.5)


def test_step_seconds_reads_the_variant_that_knows_the_window():
    table = _table()
    other = dict(table["jit_tnc_residual_c00"][0])
    other["ops"] = {"fusion.2": {"steps": [2], "owners": ["x"], "part": "prep",
                                 "opcode": "fusion"}}
    table["jit_tnc_residual_c00"].insert(0, other)
    joined = obs.step_seconds(
        [("jit_tnc_residual_c00/fusion.2", 3.0), ("jit_tnc_residual_c00/copy.1", 2.0)],
        table,
    )
    assert joined["by_part"] == pytest.approx({"dot": 3.0, "prep": 2.0})
    # two signatures that know every op of the window (a served batch of
    # 1 and of 32): the one whose fusions all ran is the one that ran
    twin = dict(table["jit_tnc_residual_c00"][1])
    twin["ops"] = {
        **{op: dict(e, part="out") for op, e in twin["ops"].items()},
        "fusion.77": {"steps": [1], "owners": ["y"], "part": "dot", "opcode": "fusion"},
    }
    window = [(f"jit_tnc_residual_c00/{op}", 1.0)
              for op in table["jit_tnc_residual_c00"][1]["ops"]]
    for order in ([twin, table["jit_tnc_residual_c00"][1]],
                  [table["jit_tnc_residual_c00"][1], twin]):
        joined = obs.step_seconds(window, {"jit_tnc_residual_c00": order})
        assert "out" not in joined["by_part"] or joined["by_part"]["out"] == 2.0
        assert joined["by_part"]["dot"] == pytest.approx(3.0)
    # modules the program never registered: nothing to join with
    assert obs.step_seconds([("jit_run/fusion.1", 1.0)], table) is None


def test_abstract_arguments_are_recorded_once_a_trace():
    import jax
    import jax.numpy as jnp

    from tnc_tpu.ops.backends import named_jit

    calls = []

    def double(x, y):
        calls.append(1)
        return x * 2 + y

    fn = named_jit(double, "tnc_test_double")
    record = op_table.registered()["tnc_test_double"][-1]
    assert record.variants == [] and record.jitted() is fn
    x = jnp.ones((4, 8), jnp.float32)
    fn(x, x)
    assert len(calls) == 1 and len(record.variants) == 1
    (args, kwargs), = record.variants
    assert kwargs == {} and [(a.shape, str(a.dtype)) for a in args] == [
        ((4, 8), "float32")] * 2
    assert isinstance(args[0], jax.ShapeDtypeStruct)
    fn(x + 1, x)  # a second call traces nothing and records nothing
    assert len(calls) == 1 and len(record.variants) == 1
    fn(jnp.ones((2, 8), jnp.float32), jnp.ones((2, 8), jnp.float32))
    assert len(calls) == 2 and len(record.variants) == 2
    # asked for, the table compiles each traced signature again
    table = obs.device_op_table(["jit_tnc_test_double"])
    variants = table["jit_tnc_test_double"]
    assert [v["status"] for v in variants] == ["ok", "ok"]
    assert all(v["ops"] and v["steps"] == [] and v["seconds"] > 0 for v in variants)
    del fn
    import gc

    gc.collect()
    assert record.jitted() is None  # a dropped program is forgotten
    assert "jit_tnc_test_double" not in obs.device_op_table(["jit_tnc_test_double"])
