"""Shared north-star plan-cache identifiers.

Single source of truth for the plan cache key and the plan-content
fingerprint, imported by ``bench.py`` — hand-copied key construction
desyncs silently on the next version bump, and a desynced key makes a
run redo cached oracle work.
"""

from __future__ import annotations

import hashlib
import pickle

from tnc_tpu.benchmark.cache import cache_key

#: bump when planner/slicer behavior changes invalidate old plans
PLAN_SCHEME = "northstar-plan-v2"


def northstar_plan_key(
    qubits: int, depth: int, seed: int, ntrials: int, target_log2: float
) -> str:
    """Stable cache key for the north-star plan.

    >>> northstar_plan_key(53, 14, 42, 128, 29.0) == northstar_plan_key(
    ...     53, 14, 42, 128, 29.0)
    True
    >>> northstar_plan_key(53, 14, 42, 128, 29.0).endswith("hyper-target2^29")
    True
    """
    return cache_key(
        PLAN_SCHEME,
        f"sycamore-{qubits}-m{depth}-seed{seed}-trials{ntrials}",
        seed,
        1,
        f"hyper-target2^{target_log2:g}",
    )


def oracle_key(plan_key: str) -> str:
    return plan_key.replace("northstar-plan", "northstar-oracle")


def plan_fingerprint(sp) -> str:
    """Content fingerprint of a sliced plan (the compiled program +
    slicing signature): oracle artifacts are valid only for the exact
    plan they were computed from."""
    return hashlib.sha256(pickle.dumps((sp.signature(),))).hexdigest()[:16]
