"""What several per-layer readers share. A window's work is
``run.window["units"]`` slices or requests, as its traffic kind counted
them; its cost comes from the shapes of the plan's steps."""

from __future__ import annotations

from perf import reference, roofline


def window_cost(run, units: int) -> dict:
    q = run.state["question"]
    shapes = reference.plan_shapes(
        q["leaf_legs"], q["pairs"], q["leg_dims"], q["sliced_legs"],
        q.get("varying_leaves", ()),
    )
    return roofline.window_cost(shapes, units, run.peaks["on_chip_vector_bytes"])


def roofline_pct(run):
    """Least seconds of ONE chip for its share of the window's units over
    the summed device time of the ops of the traced window on the busiest
    chip. Nothing without a trace."""
    units = run.window.get("units")
    if not run.reduced or not units or not run.reduced["op_s_max"]:
        return None
    per_chip = -(-units // run.chips)
    least = roofline.least_seconds(window_cost(run, per_chip), run.peaks)
    run.window["roofline"] = least
    return 100.0 * least["seconds"] / run.reduced["op_s_max"]


def mfu_pct(run):
    """Real operations of the window's units over its seconds and the
    peak of the chips used."""
    units = run.window.get("units")
    if not units:
        return None
    peak = run.chips * run.peaks["flops_per_s"]
    return 100.0 * window_cost(run, units)["ops"] / run.window["window_s"] / peak


def idle_pct(run):
    return run.reduced["idle_pct_idlest"] if run.reduced else None
