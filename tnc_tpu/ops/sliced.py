"""Sliced contraction execution.

A :class:`SlicedProgram` pairs a reduced-metadata
:class:`~tnc_tpu.ops.program.ContractionProgram` (sliced legs removed)
with indexing instructions describing, for each input, which axes are
fixed per slice. Execution sums the program's result over all slice index
combinations.

This module owns what **one slice** is: :func:`slice_indices` (slice id
to leg indices), :func:`index_buffer` (pin a leaf's sliced axes) and
:func:`slice_body` (pin a state's sliced leaves to one row of indices
and run steps on the unbatched operands; :func:`program_slice_fn` is
the body over a whole program, by slice id). Every executor builds its
slices from them — the host loop of :mod:`tnc_tpu.ops.chunked`, the
on-device loop of :mod:`tnc_tpu.parallel.sliced_parallel`, the
partitioned executor, the gradient loop and the numpy oracle below —
so a change to how a slice runs is made once.

Slice-invariant stem hoisting (``hoist=True``): steps whose operands
depend on no sliced leg are bit-identical across slices. The hoist pass
(:mod:`tnc_tpu.ops.hoist`) splits the program into an invariant
**prelude** executed once and a per-slice **residual** program whose
extra input slots are the prelude's cached intermediates. Execution
cost drops from ``num_slices * total_flops`` to ``invariant_flops +
num_slices * residual_flops``; the slicing planner scores candidate
slice sets with the same formula
(:mod:`tnc_tpu.contractionpath.slicing`).
"""

from __future__ import annotations

import contextlib
import os
from dataclasses import dataclass, replace
from typing import Sequence

import numpy as np

from tnc_tpu import obs
from tnc_tpu.obs import op_table
from tnc_tpu.contractionpath.contraction_path import ContractionPath
from tnc_tpu.contractionpath.slicing import Slicing
from tnc_tpu.ops.program import (
    ContractionProgram,
    build_program,
    steps_bytes,
    steps_flops,
)
from tnc_tpu.ops.backends import apply_steps, run_steps_timed
from tnc_tpu.ops.split_complex import apply_steps_split
from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor


@dataclass(frozen=True)
class SlicedProgram:
    program: ContractionProgram  # over slice-reduced shapes
    slicing: Slicing
    # per input slot: ((axis_in_original_tensor, slice_position), ...)
    # ordered by axis, where slice_position indexes slicing.legs
    slot_slices: tuple[tuple[tuple[int, int], ...], ...]

    def signature(self) -> tuple:
        return (self.program.signature(), self.slicing, self.slot_slices)

    def signature_digest(self) -> str:
        """Stable hex digest of :meth:`signature` (shared canonical
        encoder) — what sliced-plan artifacts persist on disk."""
        from tnc_tpu.utils.digest import stable_digest

        return stable_digest(self.signature())


class SliceYield(Exception):
    """A sliced execution yielded voluntarily at a checkpoint boundary
    (``on_slice`` returned True): the partial accumulator is persisted
    (when a checkpoint is armed) and ``cursor`` names the next slice to
    run. Re-invoking the same call resumes bit-identically from the
    checkpoint — the mechanism behind priority preemption in
    :mod:`tnc_tpu.serve.elastic`. Not an error: the caller chose to be
    interrupted."""

    def __init__(self, cursor: int):
        super().__init__(f"sliced execution yielded at slice {cursor}")
        self.cursor = int(cursor)


def build_sliced_program(
    tn: CompositeTensor, contract_path: ContractionPath, slicing: Slicing
) -> SlicedProgram:
    """Compile ``tn``'s path with ``slicing.legs`` removed from every leaf."""
    removed = set(slicing.legs)
    position = {leg: k for k, leg in enumerate(slicing.legs)}

    slot_slices: list[tuple[tuple[int, int], ...]] = []

    def reduce_tensor(t: LeafTensor) -> LeafTensor:
        info = tuple(
            (axis, position[leg])
            for axis, leg in enumerate(t.legs)
            if leg in removed
        )
        slot_slices.append(info)
        reduced = LeafTensor(
            [l for l in t.legs if l not in removed],
            [d for l, d in t.edges() if l not in removed],
            t.data,
        )
        return reduced

    def reduce_network(tensors: Sequence) -> CompositeTensor:
        out = CompositeTensor()
        # First pass: leaves in order (matching build_program slot order),
        # composites recursed afterwards in index order.
        reduced_children: list = []
        for child in tensors:
            if isinstance(child, CompositeTensor):
                reduced_children.append(None)
            else:
                reduced_children.append(reduce_tensor(child))
        for idx, child in enumerate(tensors):
            if isinstance(child, CompositeTensor):
                reduced_children[idx] = reduce_network(child.tensors)
        for c in reduced_children:
            out.push_tensor(c)
        return out

    if contract_path.nested:
        # Slicing currently targets flat paths (the distributed layer slices
        # within partitions instead).
        raise ValueError("Sliced execution expects a flat path")

    reduced_tn = reduce_network(tn.tensors)
    program = build_program(reduced_tn, contract_path)
    if slicing.fusion is not None:
        program = replace(program, fusion=slicing.fusion)
    return SlicedProgram(program, slicing, tuple(slot_slices))


def kahan_add(s, c, x):
    """One compensated (Kahan) accumulation step over arrays.

    Returns ``(s', c')`` with ``s' + c'`` carrying the running sum to ~2
    ulp *independent of the number of steps* — the slice loop adds up to
    tens of thousands of contributions whose total cancels to orders of
    magnitude below the individual terms (a single Sycamore amplitude vs
    per-slice partial sums), where plain f32 accumulation loses the
    1e-5 parity target. XLA does not reassociate
    floating-point adds by default, so the compensation survives jit
    (verified by tests/test_kahan.py under jax.jit).

    >>> import numpy as np
    >>> s = c = np.float32(1.0)
    >>> c = np.float32(0.0)
    >>> for _ in range(100):          # plain f32 sum would stay at 1.0
    ...     s, c = kahan_add(s, c, np.float32(1e-8))
    >>> 9e-07 < float(s + c) - 1.0 < 1.1e-06
    True
    """
    y = x + c
    t = s + y
    return t, y - (t - s)


def slice_indices(dims: Sequence[int], s):
    """Leg indices of slice id ``s``, one per sliced leg: the
    mixed-radix digits of ``s`` over ``dims``, last leg fastest. The
    one rule; ``s`` may be a Python int (host loops), a numpy vector of
    ids (the chunked executor's index table: each entry is then a
    vector) or a traced scalar (the on-device loops).

    >>> slice_indices((2, 3), 5), slice_indices((2, 3), 2)
    ([1, 2], [0, 2])
    """
    idx = []
    for d in reversed(dims):
        idx.append(s % d)
        s = s // d
    idx.reverse()
    return idx


def index_buffer(xp, arr, info, indices):
    """Pin ``arr``'s sliced axes to the given slice ``indices``.

    ``info`` is the slot's ``slot_slices`` entry: ((axis, slice_pos), …)
    ordered by axis.
    """
    view = arr
    offset = 0
    for axis, pos in info:
        view = xp.take(view, indices[pos], axis=axis - offset)
        offset += 1
    return view


def slice_body(
    xp,
    steps,
    slot_slices,
    slots: Sequence[int] | None = None,
    split_complex: bool = False,
    precision: str | None = None,
    policy=None,
    interpret: bool = False,
    numbers: Sequence[int] | None = None,
):
    """``body(state, indices) -> state``: what running one slice means.

    ``state`` (a list or dict, slot -> buffer; a (real, imag) pair per
    slot in split mode) holds full sliced leaves in ``slots`` (default:
    every slot of ``slot_slices``, for a whole program; a chunk names
    the leaves that enter it whole). The body pins them to the row
    ``indices`` (:func:`slice_indices` of the slice id), then runs
    ``steps`` on the unbatched operands — the stored shapes and
    macro-transposes :mod:`tnc_tpu.ops.program` planned, nothing added
    — under ``policy`` (the kernel ladder planned over exactly these
    ``steps``; split mode). ``state`` is mutated and returned; the
    caller reads the slots it wants. With ``slots=()`` nothing is
    pinned: the steps run once on whole slots.

    Off the host oracle the pinning is traced under the named scope
    ``tnc.slice.index`` and each step under its own
    (:func:`tnc_tpu.ops.split_complex.apply_step_split`), numbered by
    ``numbers`` (the step's index in the step list of the program being
    traced; default: its index in ``steps``), so a loop body is covered
    whole: :func:`tnc_tpu.obs.device_op_table`."""
    if slots is None:
        slots = range(len(slot_slices))
    pinned = tuple((slot, slot_slices[slot]) for slot in slots)

    def pin(buf, info, indices):
        if isinstance(buf, tuple):
            return tuple(index_buffer(xp, part, info, indices) for part in buf)
        return index_buffer(xp, buf, info, indices)

    def body(state, indices):
        with slice_index_scope(xp):
            for slot, info in pinned:
                state[slot] = pin(state[slot], info, indices)
        if split_complex:
            apply_steps_split(
                xp, steps, state, precision, policy, interpret, numbers
            )
        else:
            apply_steps(xp, steps, state, numbers)
        return state

    return body


def slice_index_scope(xp):
    """The named scope of cutting the sliced leaves for one slice
    (``tnc.slice.index``; nothing on the host oracle)."""
    if xp is np:
        return contextlib.nullcontext()
    return op_table.named_scope(op_table.SLICE_INDEX)


def program_slice_fn(xp, sp: SlicedProgram, **body_options):
    """``fn(full_buffers, s) -> slice s's result`` (stored shape) of a
    whole sliced program: :func:`slice_body` over all its steps, fed
    :func:`slice_indices` of the id. ``full_buffers`` is not mutated."""
    body = slice_body(xp, sp.program.steps, sp.slot_slices, **body_options)
    dims, result_slot = sp.slicing.dims, sp.program.result_slot

    def fn(full_buffers, s):
        with slice_index_scope(xp):
            indices = slice_indices(dims, s)
        return body(list(full_buffers), indices)[result_slot]

    return fn


def execute_sliced_numpy(
    sp: SlicedProgram,
    arrays: Sequence[np.ndarray],
    dtype=np.complex128,
    max_slices: int | None = None,
    hoist: bool = False,
    ckpt: str | None = None,
    step_spans: bool | None = None,
    slice_range: tuple[int, int] | None = None,
    on_slice=None,
) -> np.ndarray:
    """CPU oracle: python loop over slices, sum of program results.

    ``max_slices`` caps the loop (partial sum) — used by benchmark
    baselines that extrapolate from a slice subset. ``hoist=True``
    computes the slice-invariant stem once and loops only the residual
    program (numerically identical — the same step kernels run in the
    same order, just not once per slice). ``ckpt`` (or ``TNC_TPU_CKPT``)
    arms slice-range checkpointing — the partial sum + cursor persist
    and an interrupted oracle run resumes bit-identically
    (:mod:`tnc_tpu.resilience.checkpoint`); minutes-per-slice oracle
    work is exactly what should never restart from slice 0.

    ``step_spans``: per-step timing spans (predicted flops/bytes next
    to measured wall time — the calibration input). Default (``None``):
    on whenever tracing is on. Callers that wall-clock this function as
    a published baseline pass ``False`` so span bookkeeping never sits
    inside their timed region (``bench.py`` takes its calibration
    sample from a separate untimed pass).

    ``slice_range=(lo, hi)``: partial sum over slice ids ``[lo, hi)``
    only — the multi-host serving shard shape (each host covers a
    contiguous range; the root sums the range partials in range order).
    Mutually exclusive with ``max_slices``. ``ckpt`` composes with a
    range since the elastic fleet (:mod:`tnc_tpu.serve.elastic`): the
    range partial checkpoints its own cursor + accumulator (signature
    includes the range), so a range shard lost to a dead worker resumes
    bit-identically on a survivor.

    ``on_slice``: optional ``cb(next_cursor) -> bool`` invoked after
    every completed slice. Returning True forces a checkpoint save (when
    armed) and raises :class:`SliceYield` — cooperative preemption at a
    slice boundary; the same call re-invoked resumes from the
    checkpoint.
    """
    from tnc_tpu.resilience import checkpoint as _ckpt
    from tnc_tpu.resilience import faultinject as _faults

    full = [np.asarray(a, dtype=dtype) for a in arrays]
    if hoist:
        from tnc_tpu.ops.hoist import hoist_sliced_program, run_prelude

        hp = hoist_sliced_program(sp)
        if not hp.is_noop:
            with obs.span(
                "sliced.prelude", steps=len(hp.prelude_steps), executor="numpy"
            ) as osp:
                full = run_prelude(np, hp, full)
                if obs.enabled():
                    pre = [ps.step for ps in hp.prelude_steps]
                    osp.add(
                        flops=steps_flops(pre),
                        bytes=steps_bytes(pre, np.dtype(dtype).itemsize),
                    )
            sp = hp.residual
    acc = np.zeros(sp.program.stored_result_shape, dtype=dtype)
    one_slice = program_slice_fn(np, sp)
    num = sp.slicing.num_slices
    if max_slices is not None:
        num = min(num, max_slices)
    if slice_range is not None:
        if max_slices is not None:
            raise ValueError(
                "slice_range is mutually exclusive with max_slices"
            )
        lo, hi = slice_range
        lo = max(0, int(lo))
        hi = min(int(hi), sp.slicing.num_slices)
        ckpt_path = _ckpt.resolve_ckpt(ckpt)
        mgr = None
        start = lo
        if ckpt_path is not None:
            # the range rides the signature: a (lo, hi) shard's
            # accumulator must never resume a different shard of the
            # same program (and arrays_digest keeps different leaf data
            # — different bitstrings — apart, as in the full-run path)
            sig = _ckpt.signature_hash(
                "numpy-range-v1", sp.signature(), str(np.dtype(dtype)),
                lo, hi, hoist, _ckpt.arrays_digest(arrays),
            )
            mgr = _ckpt.SliceCheckpoint(ckpt_path, sig)
            loaded = mgr.load()
            if loaded is not None:
                start, (saved,) = loaded
                start = max(lo, min(int(start), hi))
                acc = np.asarray(saved, dtype=dtype)
        with obs.span("sliced.range", lo=lo, hi=hi):
            for s in range(start, hi):
                _faults.fault_point("sliced.slice", s=s)
                acc = acc + one_slice(full, s)
                if mgr is not None:
                    mgr.maybe_save(s + 1, lambda _a=acc: [_a])
                if on_slice is not None and s + 1 < hi and on_slice(s + 1):
                    if mgr is not None:
                        mgr.save(s + 1, [acc])
                    raise SliceYield(s + 1)
        if mgr is not None:
            mgr.finalize()
        return acc.reshape(sp.program.result_shape)
    ckpt_path = _ckpt.resolve_ckpt(ckpt)
    mgr = None
    start = 0
    if ckpt_path is not None:
        # arrays_digest: the program signature is structural — same
        # circuit with different leaf data must not cross-resume
        sig = _ckpt.signature_hash(
            "numpy-v1", sp.signature(), str(np.dtype(dtype)), num, hoist,
            _ckpt.arrays_digest(arrays),
        )
        mgr = _ckpt.SliceCheckpoint(ckpt_path, sig)
        loaded = mgr.load()
        if loaded is not None:
            start, (saved,) = loaded
            start = max(0, min(start, num))
            acc = np.asarray(saved, dtype=dtype)
    # per-step spans (predicted flops/bytes + measured wall time) are
    # on by default for the synchronous oracle under tracing — the
    # richest CPU-side calibration sample (obs.calibrate)
    step_timed = obs.enabled() and (step_spans is None or step_spans)
    pin_only = slice_body(np, (), sp.slot_slices)
    dims = sp.slicing.dims
    item_bytes = float(np.dtype(dtype).itemsize)
    with obs.span("sliced.residual", executor="numpy") as osp:
        for s in range(start, num):
            _faults.fault_point("sliced.slice", s=s)
            if step_timed:
                # a span per step: pin only (no steps), then the timed walk
                contrib = run_steps_timed(
                    np, sp.program, pin_only(list(full), slice_indices(dims, s)),
                    item_bytes,
                )
            else:
                contrib = one_slice(full, s)
            acc = acc + contrib
            if mgr is not None:
                mgr.maybe_save(s + 1, lambda _a=acc: [_a])
            if on_slice is not None and s + 1 < num and on_slice(s + 1):
                if mgr is not None:
                    mgr.save(s + 1, [acc])
                raise SliceYield(s + 1)
        if obs.enabled():
            osp.add(
                slices=num - start,
                flops=(num - start) * steps_flops(sp.program.steps),
                bytes=(num - start)
                * steps_bytes(sp.program.steps, item_bytes),
            )
    if mgr is not None:
        mgr.finalize()
    return acc.reshape(sp.program.result_shape)


_PAR_STATE: dict = {}


def _par_init(blob):
    import pickle
    import zlib

    sp, _PAR_STATE["arrays"] = pickle.loads(zlib.decompress(blob))
    _PAR_STATE["one_slice"] = program_slice_fn(np, sp)


def _par_slice(s: int):
    return np.asarray(_PAR_STATE["one_slice"](_PAR_STATE["arrays"], s))


def sliced_partials_numpy(
    sp: SlicedProgram,
    arrays: Sequence[np.ndarray],
    dtype=np.complex128,
    slice_ids: Sequence[int] | None = None,
    workers: int | None = None,
    hoist: bool = False,
) -> np.ndarray:
    """Per-slice CPU-oracle results, stacked ``(n,) + result_shape``.

    Slices are embarrassingly independent, so on a many-core host they
    fan out over a spawn-safe process pool (the same discipline as the
    SA search pool, ``repartitioning/simulated_annealing.py`` — fork is
    unsafe once JAX's runtime threads exist); on a 1-core host the loop
    runs serially. Returning *per-slice* results (not the sum) lets the
    benchmark cache the oracle on disk and serve any prefix-sum parity
    sample later without redoing minutes-per-slice numpy work.
    ``hoist=True`` runs the invariant stem once
    in this process and ships only the residual program (plus cached
    intermediates) to the pool workers."""
    import concurrent.futures
    import multiprocessing
    import pickle
    import zlib

    ids = (
        list(slice_ids)
        if slice_ids is not None
        else list(range(sp.slicing.num_slices))
    )
    full = [np.asarray(a, dtype=dtype) for a in arrays]
    if hoist:
        from tnc_tpu.ops.hoist import hoist_sliced_program, run_prelude

        hp = hoist_sliced_program(sp)
        if not hp.is_noop:
            full = [np.asarray(a) for a in run_prelude(np, hp, full)]
            sp = hp.residual
    if workers is None:
        workers = min(os.cpu_count() or 1, len(ids))
    parts: list[np.ndarray] | None = None
    if workers > 1 and len(ids) > 1:
        blob = zlib.compress(pickle.dumps((sp, full)), 1)
        try:
            ctx = multiprocessing.get_context("spawn")
            with concurrent.futures.ProcessPoolExecutor(
                max_workers=workers, mp_context=ctx, initializer=_par_init,
                initargs=(blob,),
            ) as pool:
                parts = list(pool.map(_par_slice, ids))
        except Exception:  # pool/pickle failure: the serial oracle is law
            parts = None
    if parts is None:
        one_slice = program_slice_fn(np, sp)
        parts = [np.asarray(one_slice(full, s)) for s in ids]
    shape = (len(ids),) + tuple(sp.program.result_shape)
    return np.stack(parts).reshape(shape)


def execute_sliced_numpy_parallel(
    sp: SlicedProgram,
    arrays: Sequence[np.ndarray],
    dtype=np.complex128,
    max_slices: int | None = None,
    workers: int | None = None,
    hoist: bool = False,
) -> np.ndarray:
    """Sum of :func:`sliced_partials_numpy` over the first ``max_slices``
    slices — the process-parallel analogue of
    :func:`execute_sliced_numpy`."""
    num = sp.slicing.num_slices
    if max_slices is not None:
        num = max(1, min(num, max_slices))
    parts = sliced_partials_numpy(
        sp, arrays, dtype=dtype, slice_ids=range(num), workers=workers,
        hoist=hoist,
    )
    return np.sum(parts, axis=0, dtype=dtype)
