"""Stem fusion: re-associate small operands that meet a large value one
after another, so the large value is streamed once.

A sliced Sycamore-class program spends its time in passes over memory,
not in multiply-adds: a step that contracts a value of 2^23 elements
with a 4 x 4 block reads and writes 64 MiB twice to do a few thousand
operations an element. Where the plan applies one small tensor after
another to the same large one, ``((S·W1)·W2)·W3``, the products are
associative: ``S·(W1·W2·W3)`` streams ``S`` once. State-vector
simulators call this gate fusion; here the stem is the state.

The pass runs on the FINAL ssa path, after the last ``reconfigure``
(which minimises multiply-adds and so un-fuses by construction), and
reads shapes only. It trades multiply-adds for passes: the joined step
does more arithmetic than the steps it replaces, inside one tile of the
MXU, where it is free. A search that ranks plans by multiply-adds must
therefore not see it (``slice_and_reconfigure(fuse=False)``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Sequence

from tnc_tpu import obs

__all__ = ["fuse_stem_operands", "stem_bounds"]


def stem_bounds() -> tuple[int, int]:
    """``(stem_min, joined_max)``: a stem holds at least
    ``_STAGED_MIN_SIZE`` elements (where a streamed operand starts to be
    tiled: smaller programs keep their plans); the joined small tensor
    contracts at most ``BLOCK_MAX_CONTRACT / 2`` with the stem (the
    fused step is still one real dot) and adds at most as much: its
    2 x 2 real block is one 128 x 128 tile of the MXU at most. On the
    v5e a Sycamore-53 slice takes 12.75 ms unfused, 11.18 / 11.05 /
    9.28 ms with at most 16 / 32 / 64 added, and 9.74 with 64 added and
    32 contracted (PERF.md, PR 36)."""
    from tnc_tpu.ops.program import _STAGED_MIN_SIZE
    from tnc_tpu.ops.split_complex import BLOCK_MAX_CONTRACT

    return _STAGED_MIN_SIZE, BLOCK_MAX_CONTRACT // 2


def _extent(legs, dims) -> int:
    return math.prod(dims[leg] for leg in legs)


def _walk(leaf_legs, dims, ssa_pairs, stem_min):
    """Leg sets and sizes of every value of an ssa path, and its counts:
    large steps (an operand of at least ``stem_min`` elements), the
    elements those steps stream (larger operand in, result out) and the
    multiply-adds of every step."""
    legs = list(leaf_legs)
    size = [_extent(l, dims) for l in legs]
    counts = {"large_steps": 0, "streamed_elems": 0, "macs": 0}
    for a, b in ssa_pairs:
        legs.append(legs[a] ^ legs[b])
        size.append(_extent(legs[-1], dims))
        counts["macs"] += _extent(legs[a] | legs[b], dims)
        biggest = max(size[a], size[b])
        if biggest >= stem_min:
            counts["large_steps"] += 1
            counts["streamed_elems"] += biggest + size[-1]
    return legs, size, counts


@dataclass
class _Run:
    """Stem steps that each consume the result of the one before."""

    stem: int  # ssa id of the large value the run starts from
    stem_left: bool  # the stem is the left operand of its step
    steps: list[int]  # indices of the run's steps in the path
    smalls: list[int]  # ssa ids of the small operands, in order
    joined: frozenset  # legs of the product of the small operands


def fuse_stem_operands(
    inputs: Sequence,
    ssa_pairs: Sequence[tuple[int, int]],
    sliced_legs: Sequence[int] = (),
) -> tuple[list[tuple[int, int]], dict]:
    """Re-associate runs of stem steps of an ssa path over ``inputs``.

    A **stem step** contracts a value ``S`` of at least
    2^18 elements (:func:`stem_bounds`) with a small one ``W``: at most
    ``BLOCK_MAX_CONTRACT / 2`` contracted (the step runs as one real
    dot) and at most as much added. A run of stem steps,
    each consuming the result of the one before, becomes
    ``S·(W1·…·Wj)`` for as long as the JOINED small tensor keeps both
    bounds: legs it shares with ``S`` count as contracted, legs two of
    the ``W`` share are contracted between them and count in neither.
    Where the next ``W`` would break a bound the run is closed and a
    new one starts at its result. Sizes are taken in the sliced model
    (a leg of ``sliced_legs`` has extent 1). Steps with two large
    operands, steps above the one-dot bound and stems under the
    minimum are never touched; every other step keeps its place, the
    fused steps take the place of the last step of their run, and the
    number of steps does not change.

    Returns ``(ssa_pairs, report)``; the report counts what the pass
    did: ``groups``, ``steps_removed`` (large steps that became small
    products) and ``large_steps`` / ``streamed_elems`` / ``macs`` as
    ``[before, after]`` a slice.

    >>> from tnc_tpu.tensornetwork.tensor import LeafTensor
    >>> stem = LeafTensor(list(range(18)), [2] * 18)
    >>> gates = [LeafTensor([q, 20 + q], [2, 2]) for q in (0, 1)]
    >>> pairs, report = fuse_stem_operands([stem] + gates, [(0, 1), (3, 2)])
    >>> pairs, report["groups"], report["streamed_elems"]
    ([(1, 2), (0, 3)], 1, [1048576, 524288])
    """
    stem_min, joined_max = stem_bounds()
    n = len(inputs)
    ssa_pairs = [(int(a), int(b)) for a, b in ssa_pairs]
    removed = set(sliced_legs)
    dims: dict[int, int] = {}
    for t in inputs:
        dims.update(t.edges())
    leaf_legs = [
        frozenset(l for l in t.legs if l not in removed) for t in inputs
    ]
    legs, size, before = _walk(leaf_legs, dims, ssa_pairs, stem_min)

    def joined_fits(stem: int, small: frozenset) -> bool:
        shared = small & legs[stem]
        return (
            _extent(shared, dims) <= joined_max
            and _extent(small - shared, dims) <= joined_max
        )

    runs: list[_Run] = []
    open_run: dict[int, _Run] = {}  # by the ssa id of a run's result
    for t, (a, b) in enumerate(ssa_pairs):
        stem, small = (a, b) if size[a] >= size[b] else (b, a)
        if size[stem] < stem_min or not joined_fits(stem, legs[small]):
            continue
        run = open_run.pop(stem, None)
        if run is not None and joined_fits(run.stem, run.joined ^ legs[small]):
            run.steps.append(t)
            run.smalls.append(small)
            run.joined = run.joined ^ legs[small]
        else:
            run = _Run(stem, stem == a, [t], [small], legs[small])
            runs.append(run)
        open_run[n + t] = run
    groups = [run for run in runs if len(run.steps) > 1]

    last_of = {run.steps[-1]: run for run in groups}
    dropped = {t for run in groups for t in run.steps[:-1]}
    new_id: dict[int, int] = {}
    out: list[tuple[int, int]] = []

    def emit(a: int, b: int) -> int:
        out.append((new_id.get(a, a), new_id.get(b, b)))
        return n + len(out) - 1

    fresh = n + len(ssa_pairs)  # names of the small products
    for t, (a, b) in enumerate(ssa_pairs):
        if t in dropped:
            continue
        run = last_of.get(t)
        if run is None:
            new_id[n + t] = emit(a, b)
            continue
        joined = run.smalls[0]
        for w in run.smalls[1:]:
            new_id[fresh] = emit(joined, w)
            joined = fresh
            fresh += 1
        new_id[n + t] = (
            emit(run.stem, joined) if run.stem_left else emit(joined, run.stem)
        )

    after = _walk(leaf_legs, dims, out, stem_min)[2] if groups else before
    report = {
        "groups": len(groups),
        "steps_removed": sum(len(run.steps) - 1 for run in groups),
        **{key: [before[key], after[key]] for key in before},
    }
    if groups:
        obs.counter_add("plan.stem_fusion", report["groups"], kind="groups")
        obs.counter_add(
            "plan.stem_fusion", report["steps_removed"], kind="steps_removed"
        )
    return out, report
