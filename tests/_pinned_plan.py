"""The residual of the benchmark's ``sycamore53_m14`` plan, pinned.

``data/sycamore53_m14_residual.json`` holds the 105 steps the chunked
executor runs a slice (plan digest 61194d700970ffd3 at the 2^25 slice
target: 262144 slices; 149 prelude steps hoisted), as its two chunks
split them: the fields of each `PairStep`, a staged operand's op plan
by a flag (`program._staged_ops` rebuilds it from view and perm).
"""

import json
import os

from tnc_tpu.ops.program import PairStep, _staged_ops


def pinned_residual() -> list[list[PairStep]]:
    """Each chunk's step list."""
    path = os.path.join(
        os.path.dirname(__file__), "data", "sycamore53_m14_residual.json"
    )
    with open(path) as fh:
        data = json.load(fh)
    steps = []
    for row in data["steps"]:
        (lhs, rhs, a_view, a_perm, a_dot, a_cfirst, b_view, b_perm, b_dot,
         b_cfirst, swap, out_store, a_staged, b_staged) = row
        steps.append(PairStep(
            lhs, rhs, tuple(a_view), None if a_perm is None else tuple(a_perm),
            tuple(a_dot), a_cfirst, tuple(b_view),
            None if b_perm is None else tuple(b_perm), tuple(b_dot), b_cfirst,
            swap, tuple(out_store),
            _staged_ops(list(a_view), list(a_perm)) if a_staged else None,
            _staged_ops(list(b_view), list(b_perm)) if b_staged else None,
        ))
    chunks, start = [], 0
    for size in data["chunks"]:
        chunks.append(steps[start:start + size])
        start += size
    assert start == len(steps) == 105
    return chunks
