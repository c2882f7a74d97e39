"""Operations and bytes a plan needs, from the shapes of its steps alone
(``reference.plan_shapes``), and the least time a chip could take.

- operations: ``8·k·m·n`` per step (one complex multiply-add is 8 real
  operations, whatever a gauss, Strassen or fused kernel spends);
- bytes: 8 B per complex element (two float32 planes); each leaf operand
  read when its step runs, the final result written, each intermediate
  written once and read once — except an intermediate no larger than the
  chip's on-chip vector memory, which is not counted, because a fused
  kernel may keep it on the chip. That exception keeps the share under
  100 % whatever a later kernel fuses;
- a step that depends on no sliced leg (and on no per-request leaf) is
  counted ONCE for the whole window, however often the program ran it:
  a program that hoists or caches it runs it no more often than that.

Least time = max(operations / peak FLOP/s, bytes / peak bytes/s) over the
chips used; ``bound`` says which of the two it was.
"""

from __future__ import annotations

BYTES_PER_ELEMENT = 8
OPS_PER_CMAC = 8


def step_cost(step: dict, on_chip_bytes: int) -> tuple[float, float]:
    """(real operations, bytes) of one run of one step."""
    k, m, n = step["k"], step["m"], step["n"]

    def moved(elements: int, always: bool) -> int:
        size = BYTES_PER_ELEMENT * elements
        return size if always or size > on_chip_bytes else 0

    nbytes = (
        moved(k * m, step["a_leaf"]) + moved(k * n, step["b_leaf"])
        + moved(m * n, step["last"])
    )
    return float(OPS_PER_CMAC * k * m * n), float(nbytes)


def window_cost(shapes: list[dict], units: int, on_chip_bytes: int) -> dict:
    """Cost of ``units`` slices (or requests): the varying steps ``units``
    times, the others once."""
    ops = nbytes = 0.0
    for step in shapes:
        o, b = step_cost(step, on_chip_bytes)
        times = units if step["varies"] else 1
        ops += times * o
        nbytes += times * b
    return {"ops": ops, "bytes": nbytes}


def least_seconds(cost: dict, peaks: dict, chips: int = 1) -> dict:
    by_ops = cost["ops"] / (chips * peaks["flops_per_s"])
    by_bytes = cost["bytes"] / (chips * peaks["hbm_bytes_per_s"])
    return {"seconds": max(by_ops, by_bytes),
            "bound": "operations" if by_ops >= by_bytes else "bytes",
            "by_ops_s": by_ops, "by_bytes_s": by_bytes}
