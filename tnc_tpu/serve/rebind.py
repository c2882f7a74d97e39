"""Bra rebinding: many bitstrings through one compiled program.

An amplitude network's *structure* is bitstring-independent — the
planner's path, the compiled :class:`~tnc_tpu.ops.program.
ContractionProgram`, its signature (and therefore the jit cache key),
and every gate leaf are shared by all ``2^n`` bitstrings; only the
2-element ⟨0|/⟨1| bra leaves differ. This module treats the program as
a reusable symbolic expression bound to fresh bra leaf data per request
(the EinExprs view, arXiv:2403.18030): a :class:`BoundProgram` is built
once per circuit structure and each query is O(contract-residual) — no
replanning, no retracing.

Batching: ``B`` bitstrings stack their one-hot bras along a new leading
batch leg. The primary path *threads that leg through the affected
PairSteps* — :func:`thread_batch` marks, per step, which operands carry
it, and :func:`apply_step_batched` issues one batched matmul per
touched step (``xp.matmul`` broadcasts the un-batched operand), so the
whole batch is one dispatch and steps the batch leg never reaches run
exactly once. Per-batch-entry GEMMs see the same operands in the same
order as the singleton program, so on the numpy oracle a batch of B
bit-compares to B sequential contractions (pinned by
``tests/test_serve.py``). A step that cannot carry the leg (its
batched operand has a staged device prep plan, whose op shapes are
baked flat) degrades the whole program to the vmap/stacked-dispatch
fallback (:meth:`JaxBackend.execute_batched` on device, a per-entry
loop on the host oracle).
"""

from __future__ import annotations

import logging
import threading
from collections import OrderedDict
from dataclasses import dataclass, field, replace
from typing import Any, Iterable, Sequence

import numpy as np

from tnc_tpu import obs
from tnc_tpu.builders.circuit_builder import BASIS_STATES, AmplitudeTemplate
from tnc_tpu.ops.backends import Backend, JaxBackend, NumpyBackend, named_jit
from tnc_tpu.ops.batched import (  # noqa: F401 — re-exported serving API
    apply_step_batched,
    run_steps_batched,
    stacked_rows,
    thread_batch,
)
from tnc_tpu.ops.program import (
    ContractionProgram,
    build_program,
    flat_leaf_tensors,
)
from tnc_tpu.ops.sliced import build_sliced_program

logger = logging.getLogger(__name__)

def pow2_bucket(n: int) -> int:
    """Round a batch size up to the next power of two — THE bucketing
    rule for batched serving shapes: XLA compiles one executable per
    padded batch shape (below), and the SLO drift detector groups
    dispatch measurements by the same rule
    (:func:`tnc_tpu.serve.service.batch_bucket`) so its buckets stay in
    one-to-one correspondence with compiled executables.

    >>> [pow2_bucket(n) for n in (1, 2, 3, 8, 9)]
    [1, 2, 4, 8, 16]
    """
    return 1 << max(int(n) - 1, 0).bit_length()


def stacked_bras(batch_bits: Sequence[str]) -> np.ndarray:
    """One-hot bra values for a batch: ``(B, n_det, 2)``, qubit order.
    Values come from the builder's canonical
    :data:`~tnc_tpu.builders.circuit_builder.BASIS_STATES` table (one
    definition for kets, bras and sweep values alike).

    >>> stacked_bras(["01"]).tolist()[0]
    [[(1+0j), 0j], [0j, (1+0j)]]
    """
    return np.stack(
        [np.stack([BASIS_STATES[c] for c in bits]) for bits in batch_bits]
    )


# One traced threaded-batch executable per (program, flags); retraces
# per batch size like the vmap path. Locked: services dispatch from a
# worker thread while tests touch the cache from the main thread.
_THREADED_JIT_CACHE: "OrderedDict[tuple, Any]" = OrderedDict()
_THREADED_JIT_CACHE_MAX = 128
_THREADED_JIT_LOCK = threading.Lock()


def _jit_threaded(program: ContractionProgram, flags) -> Any:
    import jax.numpy as jnp

    key = (program.signature(), flags)
    with _THREADED_JIT_LOCK:
        fn = _THREADED_JIT_CACHE.get(key)
        if fn is not None:
            _THREADED_JIT_CACHE.move_to_end(key)
    obs.counter_add(
        "jit_cache.hit" if fn is not None else "jit_cache.miss"
    )
    if fn is None:

        def run(buffers):
            return run_steps_batched(jnp, program, list(buffers), flags)

        fn = named_jit(run, "tnc_program_threaded")
        with _THREADED_JIT_LOCK:
            _THREADED_JIT_CACHE[key] = fn
            while len(_THREADED_JIT_CACHE) > _THREADED_JIT_CACHE_MAX:
                _THREADED_JIT_CACHE.popitem(last=False)
    return fn


@dataclass
class BoundProgram:
    """A compiled amplitude program with rebindable bra leaves.

    Built once per circuit *structure* (:func:`bind_template`); each
    :meth:`amplitudes` call swaps per-request bra values into the bra
    slots and dispatches — no replanning, no retracing (the program
    signature, and therefore every jit cache key, is shared).
    """

    template: AmplitudeTemplate
    program: ContractionProgram
    arrays: list[np.ndarray]  # leaf data; bra slots hold placeholders
    bra_slots: tuple[int, ...]  # one per determined qubit, qubit order
    batch_flags: tuple[tuple[bool, bool], ...]
    threadable: bool  # batch leg threads through every touched step
    plan: dict = field(default_factory=dict)  # plan-cache record (if any)
    # the budget this structure was planned under (part of the cache
    # key): a replanner must re-plan under the SAME budget for the swap
    # to be safe
    target_size: float | None = None
    # HBM-constrained structures carry a sliced plan: each request runs
    # the slice loop (stacked dispatch; the batch leg stops here)
    sliced: Any = None  # SlicedProgram | None
    # cross-request reuse (bind_template(..., reuse_store=)): `program`
    # is then the per-request RESIDUAL and the cached-subtree inputs are
    # materialized per backend environment from the content-addressed
    # store (see tnc_tpu.serve.reuse)
    reuse: Any = None  # ReuseBinding | None

    @property
    def result_shape(self) -> tuple[int, ...]:
        return tuple(self.program.result_shape)

    @property
    def result_legs(self) -> tuple[int, ...]:
        """The open legs in the order :meth:`amplitudes` returns their
        axes: the result-leg order of the executable that runs, which is
        the slice loop's program for a sliced structure (its pair steps
        are built without the sliced legs, so its order need not be
        ``program.result_legs``) and the flat program otherwise. The
        planner chooses it; ``template.permutor`` lists the same legs in
        qubit order."""
        executable = self.program if self.sliced is None else self.sliced.program
        return tuple(executable.result_legs)

    def _serving_arrays(self, backend) -> list[np.ndarray]:
        """The request-invariant input arrays for ``backend``: the bound
        leaf data, or — under cross-request reuse — the residual's
        inputs with cached subtrees materialized (store-first) for this
        backend's numeric environment."""
        if self.reuse is None:
            return self.arrays
        return self.reuse.arrays_for(backend)

    def _batch_buffers(
        self, batch_bits: Sequence[str], arrays: Sequence[np.ndarray]
    ) -> list[np.ndarray]:
        bras = stacked_bras(batch_bits)  # (B, n_det, 2)
        buffers = list(arrays)
        for i, slot in enumerate(self.bra_slots):
            buffers[slot] = np.ascontiguousarray(bras[:, i])
        return buffers

    def amplitudes(
        self,
        bitstrings: Sequence[str | Iterable],
        backend: Backend | None = None,
    ) -> np.ndarray:
        """Amplitudes for a batch of request bitstrings, one dispatch.

        Returns ``(B,) + result_shape`` — scalar amplitudes for fully
        determined templates. Open-leg axes come back in
        :attr:`result_legs` order, on every branch (fully open or with
        bras, sliced or not): an order the plan chooses, NOT qubit
        order. :mod:`tnc_tpu.queries.amplitude_batch` is the entry that
        returns them by qubit. On the numpy backend the batched result
        bit-compares to B sequential singleton contractions.
        """
        return self.amplitudes_det(
            [self.template.request_bits(b) for b in bitstrings], backend
        )

    def amplitudes_det(
        self,
        batch_bits: Sequence[str],
        backend: Backend | None = None,
        slice_range: tuple[int, int] | None = None,
        ckpt: str | None = None,
        on_slice=None,
    ) -> np.ndarray:
        """:meth:`amplitudes` over already-validated determined-position
        bit strings (``template.request_bits`` output) — the service
        dispatches these directly so per-request validation runs once,
        at admission, not again on the batching hot path. Open-leg axes
        follow the request axis in :attr:`result_legs` order (see
        :meth:`amplitudes`; by qubit:
        :mod:`tnc_tpu.queries.amplitude_batch`).

        ``slice_range=(lo, hi)`` (sliced structures only): each
        request's amplitude is the **partial sum** over that contiguous
        slice shard — the multi-host serving shape, where every host
        covers a range and the root adds the range partials in range
        order (:mod:`tnc_tpu.serve.multihost`).

        ``ckpt`` / ``on_slice`` (sliced structures, backends with
        ``supports_slice_hooks``): slice-boundary checkpointing and
        cooperative preemption for the elastic serving layer
        (:mod:`tnc_tpu.serve.elastic`) — a killed or preempted slice
        loop resumes bit-identically from its persisted cursor. Silently
        dropped on backends without the hooks (the run is then simply
        not resumable)."""
        if backend is None:
            backend = NumpyBackend()
        if slice_range is not None and self.sliced is None:
            raise ValueError(
                "slice_range only applies to sliced structures "
                "(this bound program has no slicing)"
            )
        if not getattr(backend, "supports_slice_hooks", False):
            ckpt = None
            on_slice = None
        if not batch_bits:
            return np.zeros((0,) + self.result_shape, dtype=np.complex128)
        with obs.phase("serve.bind", batch=len(batch_bits)):
            arrays = self._serving_arrays(backend)
            if self.bra_slots:
                buffers = self._batch_buffers(batch_bits, arrays)
        if not self.bra_slots:
            # fully-open template: every request is the same statevector
            if self.sliced is not None:
                # the slice loop (not the flat program) is the
                # executable for a sliced structure — and a range shard
                # must return the range PARTIAL, never the full sum
                # (the root adds one partial per host)
                kw = {} if slice_range is None else {"slice_range": slice_range}
                if ckpt is not None:
                    kw["ckpt"] = ckpt
                if on_slice is not None:
                    kw["on_slice"] = on_slice
                out = np.asarray(
                    backend.execute_sliced(self.sliced, list(arrays), **kw)
                )
            else:
                out = np.asarray(
                    backend.execute(self.program, list(arrays))
                )
            return np.broadcast_to(out, (len(batch_bits),) + out.shape).copy()
        b = len(batch_bits)

        if self.sliced is not None:
            # sliced structures: one slice-loop execution per request
            # (stacked dispatch — the batch leg would multiply the
            # already-HBM-bound per-slice peak)
            obs.counter_add("serve.rebind.dispatch", mode="sliced")
            # kwarg only when actually sharding: a backend subclass
            # predating slice_range keeps serving whole-range requests
            kw = {} if slice_range is None else {"slice_range": slice_range}
            if ckpt is not None:
                kw["ckpt"] = ckpt
            if on_slice is not None:
                kw["on_slice"] = on_slice
            return stacked_rows(
                lambda per: backend.execute_sliced(self.sliced, per, **kw),
                buffers, self.bra_slots, b, self.result_shape,
            )

        if isinstance(backend, NumpyBackend):
            obs.counter_add(
                "serve.rebind.dispatch",
                mode="threaded" if self.threadable else "loop",
            )
            out = backend.execute_batched(self.program, buffers, self.bra_slots)
            return out.reshape((b,) + self.result_shape)

        if isinstance(backend, JaxBackend):
            if self.threadable and not backend.split_complex:
                from tnc_tpu.ops.backends import place_buffers

                obs.counter_add("serve.rebind.dispatch", mode="threaded")
                # bucket the batch axis to the next power of two (pad
                # with copies of the last request, sliced off below):
                # XLA compiles one executable per shape, and service
                # traffic otherwise produces a fresh trace per distinct
                # batch size
                padded = pow2_bucket(b)
                if padded != b:
                    obs.counter_add("serve.rebind.batch_padded")
                    for slot in self.bra_slots:
                        fill = np.broadcast_to(
                            buffers[slot][-1], (padded - b, 2)
                        )
                        buffers[slot] = np.concatenate(
                            [buffers[slot], fill]
                        )
                with obs.phase("backend.lookup"):
                    fn = _jit_threaded(self.program, self.batch_flags)
                # gate leaves are bitstring-invariant and stay resident
                # behind place_buffers (the jitted fn never donates);
                # only the bras transfer per batch
                dev = place_buffers(
                    buffers, backend.dtype, False, backend.device,
                    transient=self.bra_slots,
                )
                with obs.phase("backend.execute"):
                    res = fn(dev)
                with obs.phase("backend.fetch"):
                    out = np.asarray(res)[:b]
                return out.reshape((b,) + self.result_shape)
            obs.counter_add("serve.rebind.dispatch", mode="vmap")
            out = backend.execute_batched(
                self.program, buffers, self.bra_slots
            )
            return np.asarray(out).reshape((b,) + self.result_shape)

        # unknown backend: stacked dispatch (same results, B dispatches)
        obs.counter_add("serve.rebind.dispatch", mode="loop")
        return stacked_rows(
            lambda per: backend.execute(self.program, per),
            buffers, self.bra_slots, b, self.result_shape,
        )


def plan_signature(bound: BoundProgram) -> str:
    """The *plan* identity of a bound structure: the pre-split program's
    signature digest. Under cross-request reuse ``bound.program`` is the
    residual — whose signature depends on the store split, not just the
    plan — so replanner/watcher identity checks go through here.

    >>> # cold bindings: identical to program.signature_digest()
    """
    if bound.reuse is not None:
        return bound.reuse.cold_signature
    return bound.program.signature_digest()


# A budgeted plan of a structure with more leaves than this is searched on
# its rank>=3 cores (plan_structure). Below it the slicer's bounded repair
# passes reach the whole tree either way, and plans stay what they were.
CORE_PLAN_MIN_LEAVES = 256


def _budget_cores(tn):
    """The rank>=3 cores a budgeted plan is searched on: ``(prefix,
    core_ids, next_id, core network)`` after every rank<=2 leaf (kets,
    bras, one-qubit gates, observable and trace closures) is absorbed
    into a neighbour — structure only, no data; ``prefix`` holds the
    absorptions as ssa pairs over the leaves of ``tn``. ``None`` where
    nothing is absorbed or fewer than three cores are left."""
    from tnc_tpu.contractionpath.paths.hyper import _simplify
    from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor

    leaves = list(tn.tensors)
    if len(leaves) <= CORE_PLAN_MIN_LEAVES or any(
        not t.is_leaf() for t in leaves
    ):
        return None
    dims: dict[int, int] = {}
    for t in leaves:
        dims.update(t.edges())
    prefix, legs_map, next_id = _simplify(
        {i: frozenset(t.legs) for i, t in enumerate(leaves)}, dims
    )
    if not prefix or len(legs_map) < 3:
        return None
    core_ids = sorted(legs_map)
    cores = CompositeTensor(
        [LeafTensor.from_map(sorted(legs_map[i]), dims) for i in core_ids]
    )
    return prefix, core_ids, next_id, cores


def plan_structure(
    tn, pathfinder=None, target_size: float | None = None, cost_model=None
):
    """Plan one amplitude structure: find a path, slice to the budget
    when needed, compile. Returns ``(path, slicing, program,
    sliced_program, result)`` — the shared planning step behind
    :func:`bind_template`'s cache-miss branch and the background
    replanner (:mod:`tnc_tpu.serve.replan`), so both produce plans with
    identical semantics and cache records.

    A slicing-aware pathfinder (the Hyperoptimizer's joint mode)
    exposes its winning slice set as ``last_slicing``; the budget
    repair here is then *seeded* with it — a thin validation pass over
    the plan the search already priced, not a fresh post-pass slicing
    search. ``cost_model`` keeps the repair's leg scoring in the same
    predicted-seconds domain as a calibrated replanner.

    Under a budget, a structure of more than ``CORE_PLAN_MIN_LEAVES``
    leaves is searched on its rank>=3 cores (:func:`_budget_cores`)
    and the plan is lifted back to the leaves: the absorptions first,
    then the cores' path. The slicer's repair
    passes are bounded in rounds and seconds; spread over a served
    template's raw leaves (a ket, an ``rx`` and a closure to every
    core) they reach a fraction of the tree, and a 1230-leaf sandwich
    sliced to 2^26 slices where its 500 cores slice to 2^20.

    The path handed out, sliced or not, is **re-associated for passes
    over memory** (:mod:`tnc_tpu.contractionpath.stem_fusion`): small
    operands that meet a value of 2^18 elements or more one after
    another are multiplied together first. Its multiply-adds are
    therefore no longer the search's minimum (``result`` still holds
    the search's own count; ``program.fusion`` says what changed); a
    plan with no such value comes back as the search left it."""
    from tnc_tpu.contractionpath.contraction_path import (
        ContractionPath,
        replace_ssa_ordering,
        ssa_replace_ordering,
    )

    if pathfinder is None:
        from tnc_tpu.contractionpath.paths import Greedy, OptMethod

        pathfinder = Greedy(OptMethod.GREEDY)
    cores = _budget_cores(tn) if target_size is not None else None
    planned = tn if cores is None else cores[3]
    result = pathfinder.find_path(planned)
    slicing = None
    fusion = None  # the report of stem fusion, where it ran
    # over `planned`, where the slicer or stem fusion changed the path
    replace_pairs = None
    if target_size is not None and result.size > target_size:
        from tnc_tpu.contractionpath.slicing import slice_and_reconfigure

        seed = getattr(pathfinder, "last_slicing", None)
        replace_pairs, slicing = slice_and_reconfigure(
            list(planned.tensors), result.ssa_path.toplevel, target_size,
            cost_model=cost_model,
            seed_slices=seed.legs if seed is not None else None,
        )
        fusion = slicing.fusion
        if slicing.num_slices <= 1:
            slicing = None
    elif not result.ssa_path.nested and all(
        t.is_leaf() for t in planned.tensors
    ):
        # unsliced (the served template): the same re-association
        from tnc_tpu.contractionpath.stem_fusion import fuse_stem_operands

        fused, fusion = fuse_stem_operands(
            list(planned.tensors), result.ssa_path.toplevel
        )
        if fusion["groups"]:
            replace_pairs = ssa_replace_ordering(
                ContractionPath.simple(fused)
            ).toplevel
    if cores is None:
        path = (
            result.replace_path()
            if replace_pairs is None
            else ContractionPath.simple(list(replace_pairs))
        )
    else:
        # the absorptions first, then the cores' path over their ssa ids
        prefix, core_ids, next_id, _ = cores
        m = len(core_ids)
        ssa_pairs = (
            result.ssa_path.toplevel
            if replace_pairs is None
            else replace_ssa_ordering(list(replace_pairs), m)
        )

        def lifted(i: int) -> int:
            return core_ids[i] if i < m else next_id + (i - m)

        path = ssa_replace_ordering(
            ContractionPath.simple(
                prefix + [(lifted(a), lifted(b)) for a, b in ssa_pairs]
            ),
            len(tn.tensors),
        )
    program = build_program(tn, path)
    if fusion is not None:
        program = replace(program, fusion=fusion)
    sliced = (
        build_sliced_program(tn, path, slicing)
        if slicing is not None
        else None
    )
    return path, slicing, program, sliced, result


def bind_template(
    template: AmplitudeTemplate,
    pathfinder=None,
    plan_cache=None,
    target_size: float | None = None,
    reuse_store=None,
) -> BoundProgram:
    """Plan (or load a cached plan for) ``template`` and compile it into
    a :class:`BoundProgram`.

    With a :class:`~tnc_tpu.serve.plancache.PlanCache`, a repeat
    structure loads its path from disk and performs **zero pathfinding**
    (no ``plan.find_path`` span) — and since the rebuilt program's
    signature is unchanged, a warm process-level jit cache also skips
    compilation.

    ``target_size``: peak-intermediate budget (elements). When the
    planned path exceeds it, the structure is sliced
    (``slice_and_reconfigure``) and the slicing + hoist split persist
    in the plan record; serving then runs the slice loop per request.

    ``reuse_store``: an :class:`~tnc_tpu.serve.reuse.IntermediateStore`
    — the bound program is split into content-addressed cached
    subtrees plus a per-request residual; value-identical subtrees
    (shared circuit prefixes across an angle sweep) are contracted
    once store-wide and reloaded by every later binding. Results stay
    bit-identical to the cold path.
    """
    from tnc_tpu.contractionpath.contraction_path import ContractionPath

    tn = template.network
    leaves = flat_leaf_tensors(tn)
    n_det = len(template.determined)
    bra_slots = tuple(range(len(leaves) - n_det, len(leaves)))

    plan: dict = {}
    key = None
    pairs = None
    slicing = None
    if plan_cache is not None:
        # the budget is part of the key: a plan cached without (or with a
        # different) target_size must not answer this lookup
        key = plan_cache.key_for_network(tn, target_size)
        plan = plan_cache.load(key) or {}
        pairs = plan.get("pairs")
    if pairs is None:
        path, slicing, program, sliced, result = plan_structure(
            tn, pathfinder, target_size
        )
        if plan_cache is not None:
            plan = plan_cache.record_for(
                path,
                program,
                slicing=slicing,
                sliced_program=sliced,
                flops=result.flops,
                peak=result.size,
                finder=(
                    type(pathfinder).__name__
                    if pathfinder is not None
                    else "Greedy"
                ),
                target_size=target_size,
            )
            plan_cache.store(key, plan)
    else:
        try:
            path = ContractionPath.from_obj(pairs)
            slicing = plan_cache.plan_slicing(plan)
            program = build_program(tn, path)
            valid = plan_cache.validate(plan, program)
            sliced = (
                build_sliced_program(tn, path, slicing)
                if valid and slicing is not None and slicing.num_slices > 1
                else None
            )
            if sliced is not None and plan.get("sliced_sig") not in (
                None, sliced.signature_digest()
            ):
                # the sliced compilation drifted from what the plan was
                # stored with (slicer/compiler version change)
                valid = False
        except Exception as exc:  # noqa: BLE001 — any bad entry → replan
            # valid JSON but semantically corrupt (out-of-range pairs,
            # planner drift): the cache contract is degrade-to-replan,
            # never raise — and never leave the poison pill on disk
            logger.warning(
                "cached plan %s does not rebuild (%s: %s); replanning",
                key, type(exc).__name__, exc,
            )
            valid = False
        if not valid:
            plan_cache.invalidate(key)
            return bind_template(
                template, pathfinder, plan_cache, target_size, reuse_store
            )

    arrays = [leaf.data.into_data() for leaf in leaves]
    reuse = None
    if reuse_store is not None and bra_slots:
        from tnc_tpu.serve.reuse import ReuseBinding, compute_split

        split = compute_split(program, arrays, bra_slots, sliced=sliced)
        if split is not None:
            reuse = ReuseBinding(
                split, reuse_store, arrays, program.signature_digest()
            )
            program = split.residual
            sliced = split.residual_sliced
            bra_slots = split.bra_slots
            arrays = split.placeholder_arrays(reuse.base_arrays)
    flags, threadable = thread_batch(program, bra_slots)
    return BoundProgram(
        template=template,
        program=program,
        arrays=arrays,
        bra_slots=bra_slots,
        batch_flags=flags,
        threadable=threadable,
        plan=plan,
        sliced=sliced,
        target_size=target_size,
        reuse=reuse,
    )


def bind_circuit(
    circuit,
    mask: str | Iterable | None = None,
    pathfinder=None,
    plan_cache=None,
    target_size: float | None = None,
    reuse_store=None,
) -> BoundProgram:
    """``into_amplitude_template`` + :func:`bind_template` in one call
    (consumes ``circuit``, finalizer semantics)."""
    return bind_template(
        circuit.into_amplitude_template(mask), pathfinder, plan_cache,
        target_size, reuse_store,
    )
