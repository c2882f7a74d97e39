"""Chunked execution of sliced contraction programs, a batch of slices
a dispatch.

The host stays in the loop, for what the host is needed for —
checkpoints, retries, the batch-halving rung on a resource error, slice
ranges:

- the program is **split into chunks** of at most ``chunk_steps`` steps,
  each compiled separately (compile cost scales with the chunk, not the
  whole program);
- slices are dispatched in **batches of B**: a chunk program is given
  B rows of slice indices and runs them **one after another** in a
  ``lax.scan``, each row through the per-slice body of
  :func:`tnc_tpu.ops.sliced.slice_body` — the body the SPMD loop of
  :mod:`tnc_tpu.parallel.sliced_parallel` runs. B is the granularity of
  dispatches, checkpoints and retries; it does not multiply a step's
  live memory;
- the rows' results are summed on device and accumulated across batches.

Memory: one slice's intermediates are live inside a chunk; a chunk
boundary stacks B rows of the slots alive across it.

Compiled chunk functions are cached by program signature so repeated
executions (benchmark reps, amplitude sweeps) compile nothing.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Sequence

import logging

import numpy as np

from tnc_tpu import obs
from tnc_tpu.obs import op_table
from tnc_tpu.ops.backends import named_jit, place_buffers
from tnc_tpu.ops.program import (
    ContractionProgram,
    PairStep,
    steps_bytes,
    steps_flops,
)
from tnc_tpu.ops.sliced import (
    SlicedProgram,
    index_buffer,
    kahan_add,
    slice_body,
    slice_indices,
)
from tnc_tpu.resilience import checkpoint as _ckpt
from tnc_tpu.resilience import faultinject as _faults
from tnc_tpu.resilience import retry as _retry

logger = logging.getLogger(__name__)


@dataclass(frozen=True)
class ProgramChunk:
    steps: tuple[PairStep, ...]
    in_slots: tuple[int, ...]  # slots read by this chunk (alive at entry)
    out_slots: tuple[int, ...]  # slots written here and still alive at exit


def split_program(
    program: ContractionProgram, chunk_steps: int
) -> list[ProgramChunk]:
    """Split ``program.steps`` into chunks with entry/exit slot lists.

    A slot is alive at step ``i`` if it will still be *read* at some step
    >= ``i`` (or it is the result slot). Pass-through slots that a chunk
    neither reads nor writes stay host-side and never enter the jit.

    >>> from tnc_tpu.builders.circuit_builder import Circuit
    >>> from tnc_tpu.tensornetwork.tensordata import TensorData
    >>> from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    >>> c = Circuit(); reg = c.allocate_register(3)
    >>> c.append_gate(TensorData.gate("h"), [reg.qubit(0)])
    >>> for i in range(2):
    ...     c.append_gate(TensorData.gate("cx"), [reg.qubit(i), reg.qubit(i + 1)])
    >>> tn, _ = c.into_amplitude_network("111")
    >>> path = Greedy(OptMethod.GREEDY).find_path(tn).replace_path()
    >>> from tnc_tpu.ops.program import build_program
    >>> program = build_program(tn, path)
    >>> chunks = split_program(program, 3)
    >>> len(chunks), sum(len(ch.steps) for ch in chunks) == len(program.steps)
    (3, True)
    """
    steps = program.steps
    n = len(steps)
    last_read: dict[int, int] = {program.result_slot: n}
    for i, st in enumerate(steps):
        last_read[st.lhs] = max(last_read.get(st.lhs, -1), i)
        last_read[st.rhs] = max(last_read.get(st.rhs, -1), i)
    last_read[program.result_slot] = n

    chunks: list[ProgramChunk] = []
    for a in range(0, n, chunk_steps):
        b = min(a + chunk_steps, n)
        read_here: list[int] = []
        written: set[int] = set()
        seen: set[int] = set()
        for i in range(a, b):
            st = steps[i]
            # a read is "from outside" if the slot wasn't written earlier
            # in this same chunk
            for slot in (st.lhs, st.rhs):
                if slot not in written and slot not in seen:
                    read_here.append(slot)
                    seen.add(slot)
            written.add(st.lhs)
        outs = tuple(
            sorted(s for s in written if last_read.get(s, -1) >= b)
        )
        chunks.append(ProgramChunk(steps[a:b], tuple(read_here), outs))
    return chunks


# compiled plan cache: key -> (chunks, chunk_fns, row_modes).
# Locked: the distributed local phase runs one chunked runner per
# partition from a thread pool, so lookups/evictions race otherwise.
_PLAN_CACHE: "OrderedDict[tuple, tuple]" = OrderedDict()
_PLAN_CACHE_MAX = 64
_PLAN_CACHE_LOCK = threading.Lock()

# jitted prelude executables (slice-invariant stem, run once per
# execution before the chunked slice loop), cached like the plans
_PRELUDE_CACHE: "OrderedDict[tuple, Any]" = OrderedDict()
_PRELUDE_CACHE_MAX = 64


def _prelude_fn(hp, split_complex: bool, precision, interpret: bool = False):
    """jitted ``fn(prelude_input_buffers) -> cached outputs`` for a
    :class:`~tnc_tpu.ops.hoist.HoistedProgram` — one dispatch computes
    every invariant intermediate the residual program reads."""
    import jax.numpy as jnp

    from tnc_tpu.ops.backends import lanemix_env
    from tnc_tpu.ops.split_complex import complex_mult_key, dot_precision_key

    key = (
        hp.signature(),
        split_complex,
        precision,
        lanemix_env(),
        complex_mult_key() if split_complex else None,
        dot_precision_key() if split_complex else None,
        interpret,
    )
    with _PLAN_CACHE_LOCK:
        fn = _PRELUDE_CACHE.get(key)
        if fn is not None:
            _PRELUDE_CACHE.move_to_end(key)
            return fn

    from tnc_tpu.ops.hoist import prelude_step_list, run_prelude_steps

    def run(pins):
        return tuple(
            run_prelude_steps(
                jnp, hp, pins, split_complex, precision, interpret
            )
        )

    fn = named_jit(run, "tnc_prelude", steps=prelude_step_list(hp))
    with _PLAN_CACHE_LOCK:
        _PRELUDE_CACHE[key] = fn
        while len(_PRELUDE_CACHE) > _PRELUDE_CACHE_MAX:
            _PRELUDE_CACHE.popitem(last=False)
    return fn


# The last prelude run: its jitted function, the device buffers it read and
# what it returned. A value or an amplitude is hours of slices asked for in
# consecutive ranges; every such call places the same resident leaves
# (``place_buffers`` hands back the stored buffer for unchanged content), and
# the stem's products are then the ones already on the device. One entry:
# it holds what the last call held anyway, and no chunk program donates.
_LAST_PRELUDE: list = [None]


def _hoisted_inputs(
    hp, device_full, split_complex: bool, precision, interpret: bool = False
):
    """Run the prelude on device (one jitted dispatch; none where the
    last call ran it on these very buffers) and assemble the residual
    program's input buffer list from pass-through leaves and the cached
    intermediates."""
    import jax

    pins = tuple(device_full[orig] for _, orig in hp.prelude_inputs)
    fn = _prelude_fn(hp, split_complex, precision, interpret)
    flat = jax.tree.leaves(pins)
    last = _LAST_PRELUDE[0]
    if (
        last is not None
        and last[0] is fn
        and len(last[1]) == len(flat)
        and all(a is b for a, b in zip(last[1], flat))
    ):
        obs.counter_add("chunked.prelude", mode="reused")
        cached = last[2]
    else:
        obs.counter_add("chunked.prelude", mode="run")
        cached = fn(pins)
        _LAST_PRELUDE[0] = (fn, flat, cached)
    out = []
    it = iter(cached)
    for kind, ref in hp.residual_sources:
        out.append(device_full[ref] if kind == "leaf" else next(it))
    return out


def _compiled_plan(
    sp: SlicedProgram,
    batch: int,
    chunk_steps: int,
    split_complex: bool,
    precision: str | None,
    interpret: bool = False,
):
    """``(chunks, chunk_fns, row_modes)`` for one sliced program: the
    program split into chunks and one jitted function per chunk, which
    runs the rows of the ``idx`` it is given one after another in a
    ``lax.scan`` (the last folds the rows' sum into the Kahan
    accumulator). ``row_modes[ci]`` says how chunk ``ci`` runs them:
    ``"loop"``, or ``"once"`` for a chunk no sliced leg reaches. No
    slice bound is static: one set of programs serves every range.
    Built from the program alone — no array is placed — so the chunk
    functions can also be lowered on ``jax.ShapeDtypeStruct``s for a
    described device. Cached by everything a trace bakes in."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from tnc_tpu.ops.backends import lanemix_env
    from tnc_tpu.ops.split_complex import complex_mult_key, dot_precision_key

    key = (
        sp.signature(),
        batch,
        chunk_steps,
        split_complex,
        precision,
        lanemix_env(),
        complex_mult_key() if split_complex else None,
        dot_precision_key() if split_complex else None,
        interpret,
    )
    with _PLAN_CACHE_LOCK:
        hit = _PLAN_CACHE.get(key)
        if hit is not None:
            _PLAN_CACHE.move_to_end(key)
            obs.counter_add("chunk_plan_cache.hit")
            return hit
    obs.counter_add("chunk_plan_cache.miss")

    _faults.fault_point("chunked.plan")
    chunks = split_program(sp.program, chunk_steps)
    num_inputs = sp.program.num_inputs

    def body_of(numbered, slots):
        """The per-slice body over the steps of ``numbered`` — ``(number
        in the chunk, step)`` pairs — with their kernel promotion ladder
        (split mode) planned over this subsequence — a chain cannot
        cross a chunk boundary (the boundary is a dispatch anyway).
        Cached with the plan; the cache key carries complex_mult_key so
        forced/auto plans never collide."""
        steps = tuple(step for _, step in numbered)
        policy = None
        if split_complex and steps:
            from tnc_tpu.ops.split_complex import plan_kernel_steps

            policy = plan_kernel_steps(steps)
        return slice_body(
            jnp, steps, sp.slot_slices, slots, split_complex, precision,
            policy, interpret, tuple(number for number, _ in numbered),
        )

    result_shape = sp.program.stored_result_shape
    result_slot = sp.program.result_slot

    def chunk_program(chunk, leaf_in, row_in, once, rows, row_out, last):
        """The traced function of one chunk. ``once``: the steps no
        sliced leg reaches, run once a dispatch on the whole slots
        (only an unhoisted program has them). ``rows``: the steps of
        one slice, run for each ``idx`` row in turn — the step sequence
        the SPMD loop body runs. ``leaf_in`` slots enter as full sliced
        leaves and are indexed per row, ``row_in`` slots enter stacked
        (an earlier chunk's ``row_out``), every other slot is whole and
        closed over by the loop. ``once`` and ``rows`` hold ``(number in
        the chunk, step)`` pairs. The loop is traced under the named
        scope ``tnc.chunk.io`` (what is left to it once the steps and
        the pinning take their own: unstacking a row's inputs, stacking
        its outputs), the sum over rows under ``tnc.slice.sum``."""
        looped = bool(rows)
        run_once, run_row = body_of(once, ()), body_of(rows, leaf_in)

        def enter(ins):
            return run_once(dict(zip(chunk.in_slots, ins)), None)

        def one_row(whole, idx1, row_vals):
            state = dict(whole)
            state.update(zip(row_in, row_vals))
            return run_row(state, idx1)

        def scan_rows(body, init, whole, idx):
            xs = (idx, tuple(whole[slot] for slot in row_in))
            with op_table.named_scope(op_table.CHUNK_IO):
                return lax.scan(
                    lambda carry, x: body(carry, one_row(whole, *x)), init, xs
                )

        def summed(fn):
            """``fn`` traced under ``tnc.slice.sum``."""
            def scoped(*args):
                with op_table.named_scope(op_table.SLICE_SUM):
                    return fn(*args)

            return scoped

        def chunk_fn(ins, idx):
            whole = enter(ins)
            if looped:
                # per-slice outputs stacked a row at a time, for the
                # next chunk's rows
                _, ys = scan_rows(
                    lambda _, state: (None, tuple(state[s] for s in row_out)),
                    None, whole, idx,
                )
                whole.update(zip(row_out, ys))
            return tuple(whole[s] for s in chunk.out_slots)

        def last_fn(ins, idx, acc):
            # the only slot alive after the final chunk is the result:
            # the rows' sum and the compensated accumulate fold into the
            # same dispatch. The accumulator is a Kahan (sum, comp) pair
            # per part: thousands of batch contributions cancel to far
            # below the individual terms, where plain f32 accumulation
            # loses the 1e-5 parity target.
            def stored(out):
                return jax.tree.map(lambda x: x.reshape(result_shape), out)

            whole = enter(ins)
            if looped:
                # rows added one by one, in row order
                zero = summed(jax.tree.map)(
                    jnp.zeros_like,
                    (acc[0][0], acc[1][0]) if split_complex else acc[0],
                )
                total, _ = scan_rows(
                    summed(lambda total, state: (
                        jax.tree.map(
                            jnp.add, total, stored(state[result_slot])
                        ),
                        None,
                    )),
                    zero, whole, idx,
                )
            else:  # slice-independent result: b identical terms
                b = idx.shape[0]
                total = summed(jax.tree.map)(
                    lambda x: x * b, stored(whole[result_slot])
                )
            with op_table.named_scope(op_table.SLICE_SUM):
                if split_complex:
                    (sr, cr), (si, ci_) = acc
                    sr, cr = kahan_add(sr, cr, total[0])
                    si, ci_ = kahan_add(si, ci_, total[1])
                    return ((sr, cr), (si, ci_))
                return kahan_add(acc[0], acc[1], total)

        return last_fn if last else chunk_fn

    # which slots hold a per-slice value: the sliced leaves and whatever
    # is computed from one
    per_slice: set[int] = {
        slot for slot, info in enumerate(sp.slot_slices) if info
    }
    last_ci = len(chunks) - 1
    origin = sp.program.step_origin or range(len(sp.program.steps))
    chunk_fns = []
    row_modes = []
    written_before: set[int] = set()
    for ci, chunk in enumerate(chunks):
        # a sliced-leaf slot read here for the first time enters as the
        # FULL buffer; a slot id below num_inputs that an earlier chunk
        # already wrote holds an intermediate (slots are reused as
        # result holders)
        leaf_in = tuple(
            slot
            for slot in chunk.in_slots
            if slot < num_inputs
            and sp.slot_slices[slot]
            and slot not in written_before
        )
        written_before.update(step.lhs for step in chunk.steps)
        row_in = tuple(
            slot
            for slot in chunk.in_slots
            if slot in per_slice and slot not in leaf_in
        )
        once, rows = [], []  # (number in the chunk, step)
        for number, step in enumerate(chunk.steps):
            if step.lhs in per_slice or step.rhs in per_slice:
                per_slice.add(step.lhs)
                rows.append((number, step))
            else:
                once.append((number, step))
        row_out = tuple(s for s in chunk.out_slots if s in per_slice)
        fn = chunk_program(
            chunk, leaf_in, row_in, once, rows, row_out, ci == last_ci
        )
        in_rows = {number for number, _ in rows}
        chunk_fns.append(
            named_jit(
                fn,
                "tnc_residual_last"
                if ci == last_ci
                else f"tnc_residual_c{ci:02d}",
                steps=[
                    (
                        step,
                        "row" if number in in_rows else "once",
                        origin[ci * chunk_steps + number],
                    )
                    for number, step in enumerate(chunk.steps)
                ],
            )
        )
        row_modes.append("loop" if rows else "once")

    plan = (chunks, chunk_fns, tuple(row_modes))
    with _PLAN_CACHE_LOCK:
        _PLAN_CACHE[key] = plan
        while len(_PLAN_CACHE) > _PLAN_CACHE_MAX:
            _PLAN_CACHE.popitem(last=False)
    return plan


def execute_sliced_batched_jax(
    sp: SlicedProgram,
    arrays: Sequence[Any],
    batch: int = 8,
    chunk_steps: int = 64,
    split_complex: bool = True,
    precision: str | None = "float32",
    dtype: str = "complex64",
    device=None,
    enforce_budget: bool = True,
    max_slices: int | None = None,
    host: bool = True,
    hoist: bool = False,
    ckpt: str | None = None,
    slice_range: tuple[int, int] | None = None,
    interpret: bool = False,
):
    """Run a sliced program as chunked, slice-batched jitted calls.

    Returns the accumulated result: a complex ndarray (or a
    (real, imag) pair is combined before returning). ``batch`` is
    clamped to the HBM budget (see :mod:`tnc_tpu.ops.budget`; disable
    with ``enforce_budget=False``) and then to the largest divisor of
    the slice count <= the request. ``max_slices`` caps the loop (a
    partial sum over the first slices — benchmark subset mode).

    ``host=False`` returns the device-resident accumulator (a
    (real, imag) pair in split mode) in **stored** shape without any
    device→host transfer — benchmark timing must stay transfer-free.

    ``ckpt`` (or ``TNC_TPU_CKPT``) arms slice-range checkpointing:
    the accumulator + cursor persist periodically and a restarted run
    resumes bit-identically (:mod:`tnc_tpu.resilience.checkpoint`).

    ``slice_range=(lo, hi)``: partial sum over slice ids ``[lo, hi)``
    only — the multi-host serving shard shape. Mutually exclusive with
    ``max_slices`` and explicit ``ckpt`` (a range partial is already
    someone else's resume unit; an env-armed ``TNC_TPU_CKPT`` is
    ignored for range runs for the same reason).
    """
    if sp.slicing.num_slices <= 1:
        raise ValueError(
            "execute_sliced_batched_jax expects a sliced program; "
            "use JaxBackend.execute for unsliced networks"
        )
    # input-data digest for the checkpoint signature, from the HOST
    # arrays (hashing device buffers would force a D2H): a structurally
    # identical program over different leaf data must not cross-resume
    data_digest = (
        _ckpt.arrays_digest(arrays)
        if _ckpt.resolve_ckpt(ckpt) is not None
        else None
    )
    device_full = place_buffers(arrays, dtype, split_complex, device)
    acc = run_sliced_chunked_placed(
        sp,
        device_full,
        batch=batch,
        chunk_steps=chunk_steps,
        split_complex=split_complex,
        precision=precision,
        dtype=dtype,
        device=device,
        enforce_budget=enforce_budget,
        max_slices=max_slices,
        hoist=hoist,
        ckpt=ckpt,
        ckpt_data_digest=data_digest,
        slice_range=slice_range,
        interpret=interpret,
    )
    if not host:
        return acc
    with obs.phase("backend.fetch"):
        if split_complex:
            from tnc_tpu.ops.split_complex import combine_array

            return combine_array(acc[0], acc[1]).reshape(
                sp.program.result_shape
            )
        return np.asarray(acc).reshape(sp.program.result_shape)


def run_sliced_chunked_placed(
    sp: SlicedProgram,
    device_full: Sequence[Any],
    batch: int = 8,
    chunk_steps: int = 64,
    split_complex: bool = True,
    precision: str | None = "float32",
    dtype: str = "complex64",
    device=None,
    enforce_budget: bool = True,
    max_slices: int | None = None,
    hoist: bool = False,
    ckpt: str | None = None,
    ckpt_data_digest: str | None = None,
    slice_range: tuple[int, int] | None = None,
    interpret: bool = False,
):
    """Chunked slice-batched execution over already-placed device
    buffers; returns the device-resident accumulator in stored shape
    (a (real, imag) pair in split mode). The distributed local phase
    uses this directly — each partition's buffers are committed to its
    own device, so every dispatch follows the data (one chunked runner
    per device, running concurrently under async dispatch).

    ``hoist=True`` computes the slice-invariant stem once (one extra
    jitted dispatch, outputs stay device-resident) and runs the chunked
    slice loop over the residual program only."""
    import jax.numpy as jnp

    if hoist:
        from tnc_tpu.ops.hoist import hoist_sliced_program

        with obs.phase("backend.lookup"):
            hp = hoist_sliced_program(sp)
        if not hp.is_noop:
            with obs.span(
                "sliced.prelude",
                steps=len(hp.prelude_steps),
                executor="chunked",
            ) as osp:
                res_inputs = _hoisted_inputs(
                    hp, list(device_full), split_complex, precision,
                    interpret,
                )
                if obs.enabled():
                    from tnc_tpu.ops.backends import dtype_width

                    pre = [ps.step for ps in hp.prelude_steps]
                    osp.add(
                        flops=steps_flops(pre),
                        bytes=steps_bytes(pre, dtype_width(dtype)),
                    )
            return run_sliced_chunked_placed(
                hp.residual,
                res_inputs,
                batch=batch,
                chunk_steps=chunk_steps,
                split_complex=split_complex,
                precision=precision,
                dtype=dtype,
                device=device,
                enforce_budget=enforce_budget,
                max_slices=max_slices,
                hoist=False,
                ckpt=ckpt,
                ckpt_data_digest=ckpt_data_digest,
                slice_range=slice_range,
                interpret=interpret,
            )

    num = sp.slicing.num_slices
    if num <= 1:
        # a partition untouched by global slicing arrives as a 1-slice
        # program: run it straight (no batch axis exists to reduce over).
        # donate=False — the caller owns and may reuse these buffers.
        from tnc_tpu.ops.backends import jit_program

        fn = jit_program(
            sp.program, split_complex, precision, donate=False,
            interpret=interpret,
        )
        return fn(list(device_full))
    if enforce_budget:
        from tnc_tpu.ops.budget import clamp_slice_batch

        with obs.phase("backend.lookup"):
            batch = clamp_slice_batch(
                sp.program,
                batch,
                device=device,
                split_complex=split_complex,
                dtype_bytes=8 if "128" in str(dtype) else 4,
            )
    lo = 0
    if slice_range is not None:
        if max_slices is not None or ckpt is not None:
            raise ValueError(
                "slice_range is mutually exclusive with max_slices/ckpt"
            )
        lo = max(0, int(slice_range[0]))
        num = min(int(slice_range[1]), num)
        lo = min(lo, num)
    elif max_slices is not None:
        num = max(1, min(num, max_slices))
    span = max(num - lo, 1)
    batch = max(1, min(batch, span))
    while span % batch:  # largest divisor <= requested (dims are tiny)
        batch -= 1

    # slice-range checkpointing (TNC_TPU_CKPT / ckpt=): load cursor +
    # accumulator before compiling; the signature covers everything that
    # changes the accumulation sequence except the batch (the cursor is a
    # slice index, valid at any batch alignment)
    ckpt_path = _ckpt.resolve_ckpt(ckpt) if slice_range is None else None
    mgr = None
    resumed = None
    start0 = lo
    if ckpt_path is not None:
        # str(device) disambiguates the distributed local phase: two
        # structurally identical partitions share a program signature but
        # run on different devices, and must not cross-resume each
        # other's accumulator out of a shared TNC_TPU_CKPT directory.
        # ckpt_data_digest covers the leaf DATA (the program signature is
        # structural — same circuit, different bitstring, same hash); it
        # is None only on the placed-buffers entry point, whose callers
        # isolate runs by directory (per-cell TNC_TPU_CKPT)
        sig = _ckpt.signature_hash(
            "chunked-v1", sp.signature(), chunk_steps, split_complex,
            precision, str(dtype), num, str(device), ckpt_data_digest,
        )
        mgr = _ckpt.SliceCheckpoint(ckpt_path, sig)
        loaded = mgr.load()
        if loaded is not None:
            # the cursor may be unaligned to the batch (the crashed run
            # could have degraded its batch mid-range); the dispatch
            # loop below tolerates that — each range is b = min(batch,
            # num - start) slices, and the jitted chunk fns retrace
            # once for an odd tail shape
            start0, resumed = loaded
            start0 = max(0, min(start0, num))

    with obs.phase("backend.lookup"):
        chunks, chunk_fns, row_modes = _compiled_plan(
            sp, batch, chunk_steps, split_complex, precision, interpret
        )

    # per-slot slice indices of this call's rows alone, shape
    # [num - lo, n_sliced_legs]: a range at any offset of a plan of 2^31
    # slices costs its own rows (ids are int64 here; each digit is below
    # its leg's dim)
    all_indices = np.stack(
        slice_indices(sp.slicing.dims, np.arange(lo, num, dtype=np.int64)),
        axis=1,
    ).astype(np.int32)
    obs.counter_add("sliced.index_rows", num - lo)

    import jax

    def place(x):
        # born on the target device: in the multi-device local phase an
        # uncommitted array would materialize on device 0 and hop over
        # per batch
        return jax.device_put(x, device) if device is not None else jnp.asarray(x)

    part_dtype = "float64" if "128" in str(dtype) else "float32"
    stored_shape = sp.program.stored_result_shape

    def zeros(dt):  # allocated directly on the target, no device-0 hop
        if device is not None:
            return jnp.zeros(stored_shape, dtype=dt, device=device)
        return jnp.zeros(stored_shape, dtype=dt)

    if not chunks:
        # zero-step program: the result is the (sliced) leaf itself —
        # sum its first `num` slices in one dispatch
        info = sp.slot_slices[sp.program.result_slot]
        idx_all = place(all_indices)

        def leaf_sum(buf, idx):
            rows = jax.vmap(lambda i: index_buffer(jnp, buf, info, i))(idx)
            return jnp.sum(rows, axis=0).reshape(stored_shape)

        fn = named_jit(leaf_sum, "tnc_leaf_sum")
        leaf = device_full[sp.program.result_slot]
        if split_complex:
            return (fn(leaf[0], idx_all), fn(leaf[1], idx_all))
        return fn(leaf, idx_all)

    # Kahan (sum, comp) accumulator per part; finalized to sum+comp below
    if resumed is not None:
        acc = _unflatten_acc(resumed, split_complex, place)
    elif split_complex:
        acc = (
            (zeros(part_dtype), zeros(part_dtype)),
            (zeros(part_dtype), zeros(part_dtype)),
        )
    else:
        acc = (zeros(dtype), zeros(dtype))

    # TNC_TPU_SYNC_DISPATCH: force device errors to surface inside the
    # retry/degradation scope below (async dispatch otherwise raises
    # them at the NEXT use of the poisoned accumulator)
    sync = _retry.sync_dispatch()
    with obs.span(
        "sliced.residual", executor="chunked", batch=batch,
        chunks=len(chunks), rows="loop" if "loop" in row_modes else "once",
    ) as osp:
        start = start0
        dispatches = 0
        while start < num:
            b = min(batch, num - start)
            idx = place(all_indices[start - lo : start - lo + b])

            # leaf in_slots receive the FULL buffers; each chunk's jit
            # indexes them row by row and the last one folds the
            # reduction — exactly one dispatch per chunk per batch
            def _one_batch(_idx=idx, _acc=acc, _start=start, _b=b):
                _faults.fault_point("chunked.batch", start=_start, batch=_b)
                last_ci = len(chunks) - 1
                state = dict(enumerate(device_full))
                a = _acc
                for ci, (chunk, fn) in enumerate(zip(chunks, chunk_fns)):
                    ins = tuple(state[s] for s in chunk.in_slots)
                    if ci == last_ci:
                        a = fn(ins, _idx, a)
                    else:
                        outs = fn(ins, _idx)
                        for slot, buf in zip(chunk.out_slots, outs):
                            state[slot] = buf
                        for step in chunk.steps:
                            state.pop(step.rhs, None)
                if sync:
                    jax.block_until_ready(a)
                return a

            try:
                # transient failures (preemption, disconnect) retry the
                # same batch — nothing was accumulated until the last
                # chunk's dispatch returns
                acc = _retry.retry_call(_one_batch, label="chunked.batch")
            except Exception as exc:  # noqa: BLE001 — classified below
                cls = _retry.classify_exception(exc)
                if cls is _retry.FailureClass.RESOURCE and batch > 1:
                    # OOM ladder rung 1: halve the slice batch (still a
                    # divisor of num and of the current cursor) and retry
                    # this range with a recompiled chunk plan
                    batch = max(1, batch // 2)
                    logger.warning(
                        "chunked dispatch hit a resource error (%s); "
                        "degrading slice batch to %d", exc, batch,
                    )
                    obs.counter_add("resilience.degrade.batch_shrink")
                    obs.gauge_set("resilience.degrade.batch", batch)
                    chunks, chunk_fns, row_modes = _compiled_plan(
                        sp, batch, chunk_steps, split_complex, precision,
                        interpret,
                    )
                    continue
                raise
            dispatches += len(chunks)
            for mode in row_modes:
                obs.counter_add("chunked.rows", mode=mode)
            start += b
            if mgr is not None:
                mgr.maybe_save(
                    start,
                    lambda _a=acc: _flatten_acc(_a, split_complex),
                )
        if obs.enabled():
            from tnc_tpu.ops.backends import dtype_width

            osp.add(
                slices=num - start0,
                dispatches=dispatches,
                flops=(num - start0) * steps_flops(sp.program.steps),
                bytes=(num - start0)
                * steps_bytes(sp.program.steps, dtype_width(dtype)),
            )
        if mgr is not None:
            mgr.finalize()
        # fold the compensation in (two tiny dispatches, untimed-scale cost)
        if split_complex:
            (sr, cr), (si, ci) = acc
            return (sr + cr, si + ci)
        return acc[0] + acc[1]


def _flatten_acc(acc, split_complex: bool) -> list:
    """Kahan accumulator tree → flat array list (checkpoint payload)."""
    if split_complex:
        (sr, cr), (si, ci) = acc
        return [sr, cr, si, ci]
    return [acc[0], acc[1]]


def _unflatten_acc(arrs, split_complex: bool, place):
    """Checkpoint payload → device-resident Kahan accumulator tree."""
    if split_complex:
        sr, cr, si, ci = (place(a) for a in arrs)
        return ((sr, cr), (si, ci))
    s, c = (place(a) for a in arrs)
    return (s, c)
