"""Sliced contraction execution.

A :class:`SlicedProgram` pairs a reduced-metadata
:class:`~tnc_tpu.ops.program.ContractionProgram` (sliced legs removed)
with indexing instructions describing, for each input, which axes are
fixed per slice. Execution sums the program's result over all slice index
combinations.

TPU mapping: all slices share one compiled program; the JAX backend runs
the *entire* slice loop on device as a ``lax.fori_loop`` whose body
indexes the (resident-in-HBM) full inputs, runs the contraction steps,
and accumulates — no host round-trips between slices.

Slice-invariant stem hoisting (``hoist=True``): steps whose operands
depend on no sliced leg are bit-identical across slices. The hoist pass
(:mod:`tnc_tpu.ops.hoist`) splits the program into an invariant
**prelude** executed once and a per-slice **residual** program whose
extra input slots are the prelude's cached intermediates; on device the
prelude runs before the ``fori_loop``/``scan`` and its outputs stay
resident in HBM as loop constants. Execution cost drops from
``num_slices * total_flops`` to ``invariant_flops + num_slices *
residual_flops``; the slicing planner scores candidate slice sets with
the same formula (:mod:`tnc_tpu.contractionpath.slicing`).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Sequence

import numpy as np

from tnc_tpu import obs
from tnc_tpu.contractionpath.contraction_path import ContractionPath
from tnc_tpu.contractionpath.slicing import Slicing
from tnc_tpu.ops.program import (
    ContractionProgram,
    build_program,
    steps_bytes,
    steps_flops,
)
from tnc_tpu.ops.backends import _run_steps, run_steps_timed
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


def index_buffer(xp, arr, info, indices):
    """Pin ``arr``'s sliced axes to the given slice ``indices``.

    ``info`` is the slot's ``slot_slices`` entry: ((axis, slice_pos), …)
    ordered by axis. Shared by the on-device loop and chunked executors.
    """
    view = arr
    offset = 0
    for axis, pos in info:
        view = xp.take(view, indices[pos], axis=axis - offset)
        offset += 1
    return view


def _slice_indices(slicing: Slicing, s: int) -> list[int]:
    """Mixed-radix decomposition of flat slice id ``s``."""
    idx = []
    for d in reversed(slicing.dims):
        idx.append(s % d)
        s //= d
    idx.reverse()
    return idx


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
                indices = _slice_indices(sp.slicing, s)
                buffers = [
                    index_buffer(np, arr, info, indices)
                    for arr, info in zip(full, sp.slot_slices)
                ]
                acc = acc + _run_steps(np, sp.program, buffers)
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
    item_bytes = float(np.dtype(dtype).itemsize)
    with obs.span("sliced.residual", executor="numpy") as osp:
        for s in range(start, num):
            _faults.fault_point("sliced.slice", s=s)
            indices = _slice_indices(sp.slicing, s)
            buffers = [
                index_buffer(np, arr, info, indices)
                for arr, info in zip(full, sp.slot_slices)
            ]
            if step_timed:
                contrib = run_steps_timed(
                    np, sp.program, buffers, item_bytes
                )
            else:
                contrib = _run_steps(np, sp.program, buffers)
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

    _PAR_STATE["sp"], _PAR_STATE["arrays"] = pickle.loads(
        zlib.decompress(blob)
    )


def _par_slice(s: int):
    sp = _PAR_STATE["sp"]
    full = _PAR_STATE["arrays"]
    indices = _slice_indices(sp.slicing, s)
    buffers = [
        index_buffer(np, arr, info, indices)
        for arr, info in zip(full, sp.slot_slices)
    ]
    return np.asarray(_run_steps(np, sp.program, buffers))


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
        parts = []
        for s in ids:
            indices = _slice_indices(sp.slicing, s)
            buffers = [
                index_buffer(np, arr, info, indices)
                for arr, info in zip(full, sp.slot_slices)
            ]
            parts.append(np.asarray(_run_steps(np, sp.program, buffers)))
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


def make_jax_sliced_fn(
    sp: SlicedProgram,
    split_complex: bool = False,
    precision: str | None = None,
    num_slices: int | None = None,
    unroll: int = 1,
    hoist: bool = False,
    slice_range: tuple[int, int] | None = None,
    interpret: bool = False,
):
    """Build a jittable ``fn(full_buffers) -> result`` running the whole
    slice loop on device. In split mode, buffers and result are
    (real, imag) pairs of float arrays. ``num_slices`` caps the loop
    (partial sum over the first slices — benchmark subset mode).

    ``unroll > 1`` switches ``fori_loop`` for ``lax.scan(..., unroll=)``:
    XLA pessimizes while-loop bodies (~150× on the v5e north-star,
    measured in an earlier round), and an unrolled scan presents straight-line
    step groups instead — zero host dispatches per slice, chunked-class
    code inside the loop (scan handles any ``num % unroll`` remainder
    natively). Compile time grows with the unroll factor.

    ``hoist=True`` traces the slice-invariant prelude *before* the loop
    (:mod:`tnc_tpu.ops.hoist`): its outputs become loop constants — XLA
    keeps them resident in HBM — and only the residual steps run per
    iteration.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    hp = None
    if hoist:
        from tnc_tpu.ops.hoist import hoist_sliced_program

        cand = hoist_sliced_program(sp)
        if not cand.is_noop:
            hp = cand
    loop_sp = hp.residual if hp is not None else sp

    dims = sp.slicing.dims
    lo = 0
    num = sp.slicing.num_slices
    if slice_range is not None:
        # contiguous shard [lo, hi) — the multi-host serving shape
        if num_slices is not None:
            raise ValueError("slice_range and num_slices are exclusive")
        lo = max(0, int(slice_range[0]))
        num = min(int(slice_range[1]), num)
    elif num_slices is not None:
        num = max(1, min(num, num_slices))
    unroll = max(1, min(unroll, max(num - lo, 1)))

    def decompose(s):
        idx = []
        for d in reversed(dims):
            idx.append(s % d)
            s = s // d
        idx.reverse()
        return idx

    if split_complex:
        from tnc_tpu.ops.split_complex import plan_kernels, run_steps_split

        # the kernel promotion ladder over the per-slice loop body:
        # residual chains fuse into single Pallas dispatches, eligible
        # steps promote (the compiled-fn caches key on complex_mult_key,
        # so forced/auto traces never collide)
        loop_policy = plan_kernels(loop_sp.program)

        def one_slice(loop_buffers, s):
            indices = decompose(s)
            buffers = [
                (
                    index_buffer(jnp, re, info, indices),
                    index_buffer(jnp, im, info, indices),
                )
                for (re, im), info in zip(loop_buffers, loop_sp.slot_slices)
            ]
            return run_steps_split(
                jnp, loop_sp.program, buffers, precision, policy=loop_policy,
                interpret=interpret,
            )

        def add(acc, contrib):
            (sr, cr), (si, ci) = acc
            sr, cr = kahan_add(sr, cr, contrib[0])
            si, ci = kahan_add(si, ci, contrib[1])
            return ((sr, cr), (si, ci))

        def zeros(full_buffers):
            dtype = full_buffers[0][0].dtype

            def z():
                return jnp.zeros(sp.program.stored_result_shape, dtype=dtype)

            return ((z(), z()), (z(), z()))

        def finish(acc):
            (sr, cr), (si, ci) = acc
            return (sr + cr, si + ci)

    else:

        def one_slice(loop_buffers, s):
            buffers = [
                index_buffer(jnp, arr, info, decompose(s))
                for arr, info in zip(loop_buffers, loop_sp.slot_slices)
            ]
            return _run_steps(jnp, loop_sp.program, list(buffers))

        def add(acc, contrib):
            return kahan_add(acc[0], acc[1], contrib)

        def zeros(full_buffers):
            def z():
                return jnp.zeros(
                    sp.program.stored_result_shape, dtype=full_buffers[0].dtype
                )

            return (z(), z())

        def finish(acc):
            return acc[0] + acc[1]

    def prepare(full_buffers):
        """Original buffers → loop buffers (prelude traced pre-loop)."""
        if hp is None:
            return full_buffers
        from tnc_tpu.ops.hoist import run_prelude

        return run_prelude(
            jnp, hp, list(full_buffers), split_complex, precision, interpret
        )

    if unroll <= 1:

        def fn(full_buffers):
            loop_buffers = prepare(full_buffers)

            def body(s, acc):
                return add(acc, one_slice(loop_buffers, s))

            return finish(lax.fori_loop(lo, num, body, zeros(full_buffers)))

    else:

        def fn(full_buffers):
            loop_buffers = prepare(full_buffers)

            def body(acc, s):
                return add(acc, one_slice(loop_buffers, s)), None

            acc, _ = lax.scan(
                body, zeros(full_buffers), jnp.arange(lo, num), unroll=unroll
            )
            return finish(acc)

    jitted = jax.jit(fn)
    hoisted = hp is not None
    # prelude + loop live inside ONE jitted dispatch here, so a single
    # span covers both; its flop counter is the hoisted total (prelude
    # once + residual per slice)
    total_flops = (num - lo) * steps_flops(loop_sp.program.steps)
    total_elem_bytes = (num - lo) * steps_bytes(loop_sp.program.steps, 1.0)
    if hp is not None:
        pre = [ps.step for ps in hp.prelude_steps]
        total_flops += steps_flops(pre)
        total_elem_bytes += steps_bytes(pre, 1.0)

    def run(full_buffers, _jitted=jitted):
        if not obs.enabled():
            return _jitted(full_buffers)
        first = full_buffers[0]
        item = (
            2.0 * first[0].dtype.itemsize
            if isinstance(first, tuple)
            else float(first.dtype.itemsize)
        )
        with obs.span(
            "sliced.loop", hoisted=hoisted, executor="loop"
        ) as osp:
            out = _jitted(full_buffers)
            osp.add(
                slices=num,
                flops=total_flops,
                bytes=total_elem_bytes * item,
            )
            return out

    return run
