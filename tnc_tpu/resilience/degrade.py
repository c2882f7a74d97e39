"""OOM-adaptive degradation ladder for sliced execution.

When the runtime throws ``RESOURCE_EXHAUSTED``, retrying the identical
program fails identically — the program has to shrink. The ladder, from
cheapest to most invasive:

1. **Smaller slice batch** — handled *inside* the chunked executor
   (:mod:`tnc_tpu.ops.chunked`): the per-device slice batch halves
   (recompiling only the chunk plan) and the run continues from the
   current cursor, down to batch 1.
2. **Finer slicing** — handled here: re-plan through the existing
   planner hook (:func:`~tnc_tpu.contractionpath.slicing.slice_and_reconfigure`)
   at a 4× smaller element target, rebuild the sliced program, re-run.

Every rung is visible through obs (``resilience.ladder.*`` counters and
gauges, plus the warning log), so a production run that survived an OOM
says exactly how much performance it paid.
"""

from __future__ import annotations

import logging

import numpy as np

from tnc_tpu import obs
from tnc_tpu.resilience.retry import FailureClass, classify_exception

logger = logging.getLogger(__name__)


def execute_sliced_resilient(
    tn,
    contract_path,
    slicing,
    arrays=None,
    backend=None,
    max_replans: int = 2,
    max_slices: int | None = None,
    host: bool = True,
):
    """Run a sliced contraction, walking the degradation ladder on
    RESOURCE_EXHAUSTED instead of crashing.

    ``tn`` + flat ``contract_path`` + initial ``slicing`` describe the
    network exactly as :func:`~tnc_tpu.ops.sliced.build_sliced_program`
    consumes them (the network-level inputs are required because rung 2
    re-plans the slicing). Returns ``(result, slicing_used)`` — the
    slicing may be finer than requested after degradation.

    Transient failures are retried at the dispatch boundaries below this
    level; FATAL errors re-raise untouched.

    >>> import numpy as np
    >>> from tnc_tpu.contractionpath.contraction_path import ContractionPath
    >>> from tnc_tpu.contractionpath.slicing import Slicing
    >>> from tnc_tpu.ops.backends import NumpyBackend
    >>> from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor
    >>> from tnc_tpu.tensornetwork.tensordata import TensorData
    >>> rng = np.random.default_rng(0)
    >>> def mk(legs):
    ...     return LeafTensor(legs, [2] * len(legs),
    ...         TensorData.matrix(rng.standard_normal([2] * len(legs))))
    >>> tn = CompositeTensor([mk([0, 1]), mk([1, 2]), mk([2, 0])])
    >>> path = ContractionPath.simple([(0, 1), (0, 2)])
    >>> out, used = execute_sliced_resilient(
    ...     tn, path, Slicing((2,), (2,)), backend=NumpyBackend())
    >>> used.num_slices, out.shape
    (2, ())
    """
    from tnc_tpu.contractionpath.contraction_path import (
        ContractionPath,
        replace_ssa_ordering,
    )
    from tnc_tpu.contractionpath.slicing import slice_and_reconfigure
    from tnc_tpu.ops.backends import JaxBackend
    from tnc_tpu.ops.budget import program_peak_bytes
    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.ops.sliced import build_sliced_program

    if contract_path.nested:
        raise ValueError(
            "execute_sliced_resilient expects a flat path; the partitioned "
            "executors carry their own per-partition recovery"
        )
    if backend is None:
        backend = JaxBackend()
    leaves = flat_leaf_tensors(tn)
    if arrays is None:
        arrays = [np.asarray(l.data.into_data()) for l in leaves]

    sp = build_sliced_program(tn, contract_path, slicing)
    ssa = replace_ssa_ordering(list(contract_path.toplevel), len(leaves))
    target: float | None = None
    replans = 0
    with obs.span("resilience.ladder") as osp:
        while True:
            try:
                out = backend.execute_sliced(
                    sp, arrays, max_slices=max_slices, host=host
                )
                osp.set(replans=replans, slices=sp.slicing.num_slices)
                return out, sp.slicing
            except Exception as exc:  # noqa: BLE001 — classified below
                if classify_exception(exc) is not FailureClass.RESOURCE:
                    raise
                if replans >= max_replans:
                    raise
                # rung 2: re-slice finer through the planner hook
                replans += 1
                if target is None:
                    est = program_peak_bytes(sp.program)
                    target = 2.0 ** np.floor(
                        np.log2(max(est.peak_bytes / 8.0 / 4.0, 4.0))
                    )
                else:
                    target = max(target / 4.0, 4.0)
                obs.counter_add("resilience.ladder.replans")
                logger.warning(
                    "degradation ladder: RESOURCE_EXHAUSTED (%s); "
                    "re-slicing finer at target %g elements (replan %d/%d)",
                    exc, target, replans, max_replans,
                )
                pairs, new_slicing = slice_and_reconfigure(
                    leaves, ssa, target,
                    reconf_rounds=1, step_budget=None,
                    final_rounds=2, final_budget=None,
                )
                if not new_slicing.legs:
                    # target still above the peak: push it down and retry
                    target = max(target / 4.0, 4.0)
                    pairs, new_slicing = slice_and_reconfigure(
                        leaves, ssa, target,
                        reconf_rounds=1, step_budget=None,
                        final_rounds=2, final_budget=None,
                    )
                sp = build_sliced_program(
                    tn, ContractionPath.simple(pairs), new_slicing
                )
                obs.gauge_set(
                    "resilience.ladder.num_slices", new_slicing.num_slices
                )
