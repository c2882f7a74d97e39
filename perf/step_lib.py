"""Device time by plan step: the traced window's ops joined with the
program's own op table.

The reduction keeps ALL of a window's device ops in memory under the
keys ``module/op`` (``run.reduced["device_ops"]``; only the result line
cuts to ten). The program maps exactly those keys back to the steps of
its plan: ``tnc_tpu.obs.device_op_table()`` asks every jitted program
for the optimized text of its executable and reads each instruction's
``op_name``, the named scope ``tnc.step.<NNNN>.<size>.<mode>.<form>``
and its sub-scope ``prep`` | ``dot`` | ``out`` the step was traced
under; ``tnc_tpu.obs.step_seconds`` joins the two. This module does
that once a run, after the window, prints one phase line (``"phase":
"steps"``) and hands the join to the ``step_*`` metrics.

A program without the table (the parent of the PR that added it), or
one whose compiled text came from a compile-cache entry older than the
scopes (``stale``), gives every reader here ``None``.
"""

from __future__ import annotations

import statistics
import time

from perf import common

LARGE_ROW = ("large", "row")  # a stem step of the per-slice body


def _program_table(modules):
    """``(table, step_seconds)`` of the program under test, or ``None``
    where it has none."""
    try:
        from tnc_tpu import obs

        return obs.device_op_table(modules), obs.step_seconds
    except (ImportError, AttributeError):
        return None


def join(run):
    """``tnc_tpu.obs.step_seconds`` of the run's traced window, made
    once a run (the ``steps`` phase line is printed then): ``None``
    without a trace, without a table, or where the table is stale."""
    if "step_join" not in run.state:
        run.state["step_join"] = _join(run)
    return run.state["step_join"]


def _join(run):
    if not run.reduced or not run.reduced.get("device_ops"):
        return None
    ops = run.reduced["device_ops"]
    t0 = time.monotonic()
    try:
        program = _program_table({name.split("/", 1)[0] for name, _ in ops})
        if program is None:
            return None
        table, step_seconds = program
        joined = step_seconds(ops, table)
    except Exception as exc:  # noqa: BLE001 — a metric that cannot be read is left out
        common.emit({"phase": "steps", "error": repr(exc)[:400]})
        return None
    asked_s = time.monotonic() - t0
    stale = {
        module: variants[0]["why"]
        for module, variants in table.items()
        if any(v["status"] != "ok" for v in variants)
    }
    if joined is None:
        common.emit({"phase": "steps", "seconds": round(asked_s, 3),
                     "modules": sorted(table), "stale": stale})
        return None
    units = units_per_chip(run)
    common.emit({
        "phase": "steps", "seconds": round(asked_s, 3),
        "modules": {m: len(v) for m, v in sorted(table.items())},
        "total_s": joined["total_s"], "attributed_s": joined["attributed_s"],
        "mixed_s": joined["mixed_s"], "unattributed_s": joined["unattributed_s"],
        "by_part": joined["by_part"], "by_form": joined["by_form"],
        "by_mode": joined["by_mode"], "by_opcode": joined.get("by_opcode"),
        "units_per_chip": units,
        "unknown_ops": len(joined["unknown_ops"]),
        "costliest_steps": [step_line(row, units) for row in costliest(joined, 10)],
        "unattributed": [[name, round(s, 6)] for name, s in joined["unattributed"][:5]],
    })
    return joined


def units_per_chip(run):
    """Slices (or requests) one chip completed in the window: the
    reduction averages an op's seconds over the chips."""
    units = run.window.get("units")
    return units / run.chips if units else None


def step_total(row) -> float:
    """A step's device seconds: its own ops and its equal share of each
    op it shares with another step."""
    return row["seconds"] + row["mixed_s"]


def costliest(joined, n: int) -> list:
    return sorted(joined["steps"].values(), key=lambda r: -step_total(r))[:n]


def ps_per_elem(row, units):
    """Picoseconds a streamed element of one step: its device seconds
    of the window over (units a chip x elements it streams a unit)."""
    if not units or not row.get("elements"):
        return None
    return 1e12 * step_total(row) / (units * row["elements"])


def step_line(row, units) -> dict:
    ps = ps_per_elem(row, units)
    return {
        "module": row["module"], "scope": row.get("scope"),
        "plan_index": row.get("plan_index"), "runs": row.get("runs"),
        "ms_per_unit": round(1e3 * step_total(row) / units, 5) if units else None,
        "elements": row.get("elements"), "k": row.get("k"),
        "ps_per_elem": round(ps, 3) if ps is not None else None,
        "by_part_ms": {
            part: round(1e3 * s / units, 5) if units else None
            for part, s in row["by_part"].items()
        },
    }


def attributed_pct(run):
    joined = join(run)
    if not joined or not joined["total_s"]:
        return None
    return 100.0 * joined["attributed_s"] / joined["total_s"]


def part_share_pct(run, *parts: str):
    """Op seconds of ``parts`` over the attributed op seconds."""
    joined = join(run)
    if not joined or not joined["attributed_s"]:
        return None
    found = sum(joined["by_part"].get(part, 0.0) for part in parts)
    return 100.0 * found / joined["attributed_s"]


def stem_rows(joined, form: str | None = None, mode: str | None = None) -> list:
    """The ``large`` once-a-row steps of the join (of one form, of one
    mode)."""
    return [
        row for row in joined["steps"].values()
        if (row.get("size"), row.get("runs")) == LARGE_ROW
        and (form is None or row.get("form") == form)
        and (mode is None or row.get("mode") == mode)
    ]


def form_ps_per_elem(run, form: str):
    """Over the large once-a-row steps of ``form``: their op seconds in
    the window over (units a chip x elements they stream a unit)."""
    joined, units = join(run), units_per_chip(run)
    if not joined or not units:
        return None
    rows = stem_rows(joined, form=form)
    elements = sum(row["elements"] for row in rows)
    if not elements:
        return None
    return 1e12 * sum(map(step_total, rows)) / (units * elements)


def worst_ratio(run):
    """The largest ps an element of any one large once-a-row block step
    over the median of them: the step that lowered badly."""
    joined, units = join(run), units_per_chip(run)
    if not joined or not units:
        return None
    each = [
        ps for ps in (ps_per_elem(row, units) for row in stem_rows(joined, mode="block"))
        if ps
    ]
    if len(each) < 2:
        return None
    return max(each) / statistics.median(each)
