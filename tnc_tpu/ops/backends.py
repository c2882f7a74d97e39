"""Execution backends for compiled contraction programs.

The reference dispatches its pairwise kernel at build time (TBLIS vs MKL
behind the ``mkl`` cargo feature, ``README.md`` Features); here the
contractor is a runtime-pluggable backend:

- :class:`NumpyBackend` — the CPU oracle, complex128.
- :class:`JaxBackend` — the TPU path: the whole program is traced once and
  ``jax.jit``-compiled with **all input buffers donated**, so XLA reuses
  HBM for intermediates and the peak matches the analytic
  ``contract_size_tensors`` prediction. Matmuls land on the MXU; default
  dtype is complex64 (TPU has no native f64; parity target is 1e-5).
- :func:`place_buffers` — the one host-to-device placement rule, behind
  which :class:`ResidentLeaves` keeps unchanged leaves on the device.

Compiled executables are cached by program signature + dtype, so repeated
contractions of equal-shaped networks (e.g. amplitude sweeps) recompile
nothing.
"""

from __future__ import annotations

import hashlib
import logging
import os
import threading
import warnings
from collections import OrderedDict
from typing import Any, Iterable, Sequence

import numpy as np

from tnc_tpu import obs
from tnc_tpu.obs import op_table
from tnc_tpu.ops.program import ContractionProgram
from tnc_tpu.resilience import faultinject as _faults
from tnc_tpu.resilience import retry as _retry

logger = logging.getLogger(__name__)


class Backend:
    name: str = "base"
    # True when execute_sliced accepts ckpt= / on_slice= (slice-boundary
    # checkpointing + cooperative preemption); callers (the elastic
    # serving layer) only pass those kwargs when the flag is set, so a
    # backend without them keeps serving whole runs unchanged
    supports_slice_hooks: bool = False

    def execute(self, program: ContractionProgram, arrays: Sequence[Any]) -> np.ndarray:
        raise NotImplementedError

    def execute_sliced(
        self,
        sp,
        arrays: Sequence[Any],
        max_slices: int | None = None,
        host: bool = True,
        hoist: bool | None = None,
        slice_range: tuple[int, int] | None = None,
    ):
        """``slice_range=(lo, hi)``: partial sum over that contiguous
        slice shard only (the multi-host serving shape). Part of the
        backend contract — subclasses must accept it (callers only pass
        it when actually sharding, so a legacy subclass without the
        parameter keeps working for whole-range execution)."""
        raise NotImplementedError


def _lanemix_jax(x, w, idx):
    """Static permutation of the trailing ``w``-wide lane window:
    ``out[..., j] = flat[..., idx[j]]``. Executed as an exact one-hot
    matmul on the MXU (``precision=HIGHEST`` — every output element is a
    single 1.0·x product, so the result is bit-exact) or, for wide
    windows, a gather. ``TNC_TPU_LANEMIX=take`` forces the gather."""
    import jax.numpy as jnp
    from jax import lax

    x2 = x.reshape((-1, w))
    mode, cap = lanemix_env()
    if mode == "take" or w > int(cap):
        return jnp.take(x2, jnp.asarray(idx, dtype="int32"), axis=1)
    p = np.zeros((w, w), dtype=np.float32)
    p[np.asarray(idx), np.arange(w)] = 1
    pc = jnp.asarray(p, dtype=x2.dtype)
    # per-operand precision: the data side needs the full 3-term bf16
    # split to pass through exactly; the one-hot side is exact in one
    # term (every output is a single 1.0·x product) — 3 MXU passes, not 6
    return lax.dot_general(
        x2,
        pc,
        (((1,), (0,)), ((), ())),
        precision=(lax.Precision.HIGHEST, lax.Precision.DEFAULT),
    )


def _prep_operand(xp, buf, view, perm, dot_shape, ops=None):
    """Stored buffer → ``(k, free-run dims…)`` dot operand: reshape to the
    fused view, one macro transpose to (contract…, free…), and a
    leading-axes merge of the contract runs (layout-free on TPU — tiling
    only constrains trailing dims). See :mod:`tnc_tpu.ops.program`.

    When the compiler attached a staged plan (``ops``), the device path
    executes it instead — a sequence of minor-dim-safe reshapes,
    leading-dim transposes, and lane permutations that never materializes
    a tile-padded buffer (the naive path's failure mode on high-rank
    shuffles). The host oracle keeps the naive pair (same semantics)."""
    if ops is not None and xp is not np:
        x = buf
        for op in ops:
            if op[0] == "reshape":
                x = x.reshape(op[1])
            elif op[0] == "transpose":
                x = xp.transpose(x, op[1])
            else:  # ("lanemix", W, idx)
                x = _lanemix_jax(x, op[1], op[2])
        return x.reshape(dot_shape)
    v = buf.reshape(view)
    if perm is not None:
        v = xp.transpose(v, perm)
    return v.reshape(dot_shape)


def apply_step(xp, a: Any, b: Any, step, number: int = 0) -> Any:
    """One pairwise contraction; the single source of truth for the step
    kernel, shared by the whole-program, sliced-loop, and chunked
    executors.

    Device path: one ``lax.dot_general`` contracting the single leading
    ``k`` dim of both operands — XLA performs no internal relayout and
    every materialized buffer keeps a large minor dim (see
    :mod:`tnc_tpu.ops.program`), traced under the step's named scope
    (mode ``complex``: one dot of a complex dtype; ``number`` and the
    sub-scopes as :func:`tnc_tpu.ops.split_complex.apply_step_split`).
    Host path: the equivalent 2-D matmul."""
    if xp is not np:
        from tnc_tpu.ops.program import step_size_class

        scope = op_table.step_scope_name(
            number, step_size_class(step), "complex", "matrix"
        )
        op_table.note_step(number, scope)
        with op_table.named_scope(scope):
            return _device_step(xp, a, b, step)
    av = _prep_operand(xp, a, step.a_view, step.a_perm, step.a_dot, step.a_ops)
    bv = _prep_operand(xp, b, step.b_view, step.b_perm, step.b_dot, step.b_ops)
    a2 = (
        av.reshape(step.a_mat)
        if step.a_cfirst
        else av.reshape(step.a_mat[::-1]).T
    )  # (k, m)
    b2 = (
        bv.reshape(step.b_mat)
        if step.b_cfirst
        else bv.reshape(step.b_mat[::-1]).T
    )  # (k, n)
    out = (b2.T @ a2) if step.swap else (a2.T @ b2)
    return out.reshape(step.out_store)


def _device_step(xp, a, b, step):
    from jax import lax

    with op_table.named_scope("prep"):
        av = _prep_operand(
            xp, a, step.a_view, step.a_perm, step.a_dot, step.a_ops
        )
        bv = _prep_operand(
            xp, b, step.b_view, step.b_perm, step.b_dot, step.b_ops
        )
    ca = (0,) if step.a_cfirst else (len(step.a_dot) - 1,)
    cb = (0,) if step.b_cfirst else (len(step.b_dot) - 1,)
    with op_table.named_scope("dot"):
        if step.swap:
            out = lax.dot_general(bv, av, ((cb, ca), ((), ())))
        else:
            out = lax.dot_general(av, bv, ((ca, cb), ((), ())))
    with op_table.named_scope("out"):
        return out.reshape(step.out_store)


def apply_steps(xp, steps, state, numbers=None) -> None:
    """The one walker of complex-dtype steps: run ``steps`` in order
    over ``state`` (a list or dict, slot -> buffer), in place; a
    consumed slot is left ``None`` (freed eagerly). ``numbers``: each
    step's number in the program being traced (default: its index)."""
    for i, step in enumerate(steps):
        state[step.lhs] = apply_step(
            xp, state[step.lhs], state[step.rhs], step,
            numbers[i] if numbers is not None else i,
        )
        state[step.rhs] = None


def _run_steps(xp, program: ContractionProgram, buffers: list[Any]) -> Any:
    """Execute all steps; returns the result in **stored** (merged) shape —
    callers reshape to ``program.result_shape`` on the host, so the jit
    output never materializes a high-rank tile-padded array."""
    apply_steps(xp, program.steps, buffers)
    return buffers[program.result_slot]


def dtype_width(dtype) -> float:
    """Element width in bytes of a backend dtype (name string, numpy
    dtype, or anything ``np.dtype`` accepts) — the ONE rule every
    predicted-bytes computation shares (step spans, prelude/residual
    byte counters, the calibration fit). Split-complex pairs carry the
    same bytes as the complex dtype they represent, so no special case.

    >>> dtype_width("complex64"), dtype_width(np.complex128)
    (8.0, 16.0)
    """
    try:
        return float(np.dtype(dtype).itemsize)
    except TypeError:
        return 16.0 if "128" in str(dtype) else 8.0


def run_steps_timed(
    xp,
    program: ContractionProgram,
    buffers: list[Any],
    dtype_bytes: float = 16.0,
    split_complex: bool = False,
    precision: str | None = None,
    sync=None,
    policy=None,
    interpret: bool = False,
) -> Any:
    """Step-timed variant of :func:`_run_steps`: one obs span per
    :class:`~tnc_tpu.ops.program.PairStep`, named ``step[i] MxK·KxN``
    and carrying the step's *predicted* cost (``flops``, ``bytes_in``,
    ``bytes_out``) next to the span's *measured* wall time — the raw
    samples :mod:`tnc_tpu.obs.calibrate` fits its device model from.

    ``sync`` (JAX path: ``jax.block_until_ready``) forces each step's
    result before its span closes, so the measured time is device wall
    time, not async enqueue. The host oracle passes no ``sync`` — numpy
    is synchronous already. Same result contract as ``_run_steps``
    (stored shape). Must not be called under jit tracing (the spans
    would measure trace time once, not run time).

    Each span is tagged ``executor="numpy"|"jax"`` so the calibration
    fit never blends host- and device-measured samples of the same step
    into one "device" model, plus the step's shape ``bucket``
    (small/medium/stem), kernel ``mode``, and mode-credited
    ``flops_effective`` — the per-bucket MFU inputs.

    ``policy`` (a :class:`tnc_tpu.ops.split_complex.KernelPolicy`,
    split mode only): steps promote per the kernel ladder, and a fused
    chain emits ONE ``step[s..e]`` span carrying the whole run's
    summed predicted cost — the span count IS the dispatch count, so
    chain fusion is directly visible as fewer step spans.
    """
    from tnc_tpu.ops.program import step_elems, step_flops, step_label
    from tnc_tpu.ops.split_complex import (
        effective_step_flops,
        resolved_step_mode,
        step_bucket,
    )

    executor = "numpy" if xp is np else "jax"
    if not split_complex:
        policy = None

    if split_complex:
        from tnc_tpu.ops.split_complex import apply_step_split

        def kernel(a, b, st, mode=None, precision_mode=None):
            return apply_step_split(
                xp, a, b, st, precision, mode=mode,
                precision_mode=precision_mode, interpret=interpret,
            )

    else:

        def kernel(a, b, st, mode=None, precision_mode=None):
            return apply_step(xp, a, b, st)

    steps = program.steps
    chain_end = {s: e for s, e in policy.chains} if policy is not None else {}
    i = 0
    while i < len(steps):
        end = chain_end.get(i)
        if end is not None:
            from tnc_tpu.ops.split_complex import run_chain_split

            group = steps[i:end]
            # HBM traffic of ONE fused dispatch: the head's two
            # operands plus each link's non-carried operand in (PLUS
            # their prep passes — non-carried operands with a macro
            # transpose are materialized by prep_kl before entering
            # the kernel, the same read+write step_prep_elems prices
            # on single steps; only the CARRIED operand is
            # transpose-free by chain_groups' admission rule), the
            # final result out — carried intermediates live in VMEM
            # and never touch HBM, so summing per-step elems would
            # overstate the chain's bytes and bias the calibration fit
            import math as _math

            def _op_elems(view, perm, ops):
                prep = 2.0 if (perm is not None or ops) else 0.0
                return (1.0 + prep) * float(_math.prod(view))

            head = group[0]
            elems_in = _op_elems(
                head.a_view, head.a_perm, head.a_ops
            ) + _op_elems(head.b_view, head.b_perm, head.b_ops)
            run_slot = head.lhs
            for st in group[1:]:
                if st.lhs == run_slot:
                    elems_in += _op_elems(st.b_view, st.b_perm, st.b_ops)
                else:
                    elems_in += _op_elems(st.a_view, st.a_perm, st.a_ops)
                run_slot = st.lhs
            chain_rung = policy.precision_mode(i) if policy else ""
            with obs.span(
                f"step[{i}..{end - 1}] chain x{len(group)}",
                executor=executor,
                flops=sum(step_flops(st) for st in group),
                bytes_in=elems_in * dtype_bytes,
                bytes_out=step_elems(group[-1])[1] * dtype_bytes,
                # the calibrated chain ceiling can pull medium-bucket
                # steps into a chain — report the heaviest member's
                # bucket so the MFU rows stay honest
                bucket=step_bucket(max(group, key=step_flops)),
                mode="chain",
                precision=chain_rung or "default",
                flops_effective=sum(step_flops(st) for st in group),
                steps=len(group),
            ):
                out = run_chain_split(
                    xp, group, buffers, precision,
                    precision_mode=chain_rung, interpret=interpret,
                )
                if sync is not None:
                    sync(out)
            i = end
            continue
        step = steps[i]
        mode = policy.modes[i] if policy is not None else None
        precision_mode = policy.precision_mode(i) if policy is not None else None
        # tag + credit the arithmetic that actually runs: without a
        # policy the split path executes the env default (gauss, 0.75x
        # credit), never 'naive'; the complex (non-split) path is the
        # naive lowering
        resolved = (
            resolved_step_mode(step, mode) if split_complex else "naive"
        )
        if resolved == "fused_transpose":
            # the static gate can't see the live buffers: share the
            # kernel route's runtime dtype/batch predicate so spans
            # never credit a transpose pass that was actually paid
            from tnc_tpu.ops.split_complex import (
                fused_transpose_runtime_ineligible_reason,
            )

            if (
                fused_transpose_runtime_ineligible_reason(
                    buffers[step.lhs], buffers[step.rhs], step
                )
                is not None
            ):
                resolved = "naive"
        # predicted traffic credits the prep pass the resolved kernel
        # actually pays: fused_transpose streams the macro transpose
        # inside the kernel, every other mode materializes it
        elems_in, elems_out = step_elems(step, mode=resolved)
        with obs.span(
            step_label(i, step),
            executor=executor,
            flops=step_flops(step),
            bytes_in=elems_in * dtype_bytes,
            bytes_out=elems_out * dtype_bytes,
            bucket=step_bucket(step),
            mode=resolved,
            precision=(precision_mode or "default"),
            flops_effective=effective_step_flops(step, resolved),
        ):
            out = kernel(
                buffers[step.lhs], buffers[step.rhs], step, mode,
                precision_mode,
            )
            if sync is not None:
                sync(out)
        buffers[step.lhs] = out
        buffers[step.rhs] = None  # free eagerly
        i += 1
    return buffers[program.result_slot]


# Locked: the distributed local phase compiles/executes per-partition
# programs from a thread pool (parallel/partitioned.py).
_PROGRAM_JIT_CACHE: "OrderedDict[tuple, Any]" = OrderedDict()
_PROGRAM_JIT_CACHE_MAX = 256
_PROGRAM_JIT_CACHE_LOCK = threading.Lock()


def lanemix_env() -> tuple:
    """The lanemix env knobs are read at *trace* time, so every compiled
    executable must be keyed by them — otherwise flipping
    ``TNC_TPU_LANEMIX`` mid-process silently returns stale programs."""
    return (
        os.environ.get("TNC_TPU_LANEMIX", "matmul"),
        os.environ.get("TNC_TPU_LANEMIX_MATMUL_MAX", "2048"),
    )


def named_jit(fn, name: str, steps=None, sharding=None, **jit_kwargs):
    """``jax.jit(fn)`` under a stable name by role: the module is
    ``jit_<name>`` in the lowered text and in the profiler's trace,
    whatever Python happened to call the closure. The ``tnc_*`` names
    are read by ``perf/metrics`` (device seconds per program).

    The program is also remembered by that name
    (:func:`tnc_tpu.obs.op_table.register`) with what is needed to ask
    it for its compiled text later: the jitted callable, the abstract
    arguments of each trace — recorded inside the traced function, so
    only when JAX traces, never per call — and ``steps``, the step list
    it was built from as ``(step, "row" | "once", index in the plan
    handed out)`` triples in the order of the steps' scope numbers.
    ``sharding`` is put on the abstract arguments when the text is
    asked for (:func:`tnc_tpu.obs.device_op_table`)."""
    import weakref

    import jax

    record = op_table.register(name, steps, sharding)

    def traced(*args, **kwargs):
        with op_table.tracing(record, args, kwargs):
            return fn(*args, **kwargs)

    traced.__name__ = traced.__qualname__ = name
    jitted = jax.jit(traced, **jit_kwargs)
    record.jitted = weakref.ref(jitted)
    return jitted


def program_step_list(program: ContractionProgram, runs: str = "once") -> list:
    """A whole program's steps as :func:`named_jit` takes them."""
    origin = program.step_origin or range(len(program.steps))
    return [(st, runs, origin[i]) for i, st in enumerate(program.steps)]


def jit_program(
    program: ContractionProgram,
    split_complex: bool,
    precision: str | None = None,
    donate: bool = True,
    batched: frozenset[int] | None = None,
    policy=None,
    interpret: bool = False,
    role: str | None = None,
):
    """Program → jitted ``fn(buffers)`` with donated inputs; one traced
    function per (program, mode), one XLA executable per input placement.
    Shared by :class:`JaxBackend` and the distributed executors.
    LRU-bounded so long sweeps over many distinct networks don't pin
    every executable for the process lifetime.

    ``batched``: slots whose buffers carry a leading batch axis — the
    whole path is ``jax.vmap``-ed over them (amplitude sweeps,
    :meth:`JaxBackend.execute_batched`).

    **Donation rule** (a buffer of :data:`RESIDENT_LEAVES` is never
    donated): ``donate=True`` donates every input of an unbatched
    program, so its caller places them all ``transient``
    (:func:`place_buffers`); of a batched program it donates the
    ``batched`` slots only, which are transient by nature, and the
    shared slots (gate leaves, which could back no intermediate anyway)
    stay resident across batches.

    ``policy``: a :class:`tnc_tpu.ops.split_complex.KernelPolicy` —
    the per-step kernel promotion ladder the trace bakes in (split
    mode only). Part of the cache key: two policies over the same
    program are different executables. ``interpret``: Pallas interpret
    mode for the policy's kernels, from the target device
    (:func:`tnc_tpu.ops.split_complex.interpret_for`).

    ``role``: what the caller runs the program as; the module is then
    ``jit_tnc_<role>`` (``partition_local``, ``fanin_pair``) and not
    ``jit_tnc_program``, so a trace tells a distributed call's phases
    apart. Part of the cache key: a name is baked into the executable."""
    import jax

    from tnc_tpu.ops.split_complex import complex_mult_key, dot_precision_key

    if not split_complex:
        precision = None  # only the split path consumes it: one cache key
        policy = None
    key = (
        program.signature(),
        split_complex,
        precision,
        donate,
        lanemix_env(),
        complex_mult_key() if split_complex else None,
        # TNC_TPU_DOT_PRECISION is read at trace time (the per-step
        # precision resolve), so forced and auto traces must not share
        # an executable — complex_mult_key-style
        dot_precision_key() if split_complex else None,
        batched,
        policy.signature() if policy is not None else None,
        interpret,
        role,
    )
    with _PROGRAM_JIT_CACHE_LOCK:
        fn = _PROGRAM_JIT_CACHE.get(key)
        if fn is not None:
            _PROGRAM_JIT_CACHE.move_to_end(key)
    obs.counter_add("jit_cache.hit" if fn is not None else "jit_cache.miss")
    if fn is None:
        logger.debug(
            "jit: tracing program (%d steps, split_complex=%s)",
            len(program.steps),
            split_complex,
        )
        import jax.numpy as jnp

        if split_complex:
            from tnc_tpu.ops.split_complex import run_steps_split

            def run(buffers):
                return run_steps_split(
                    jnp, program, list(buffers), precision, policy=policy,
                    interpret=interpret,
                )

        else:

            def run(buffers):
                return _run_steps(jnp, program, list(buffers))

        if batched is not None:
            in_axis = (0, 0) if split_complex else 0
            axes = [
                in_axis if slot in batched else None
                for slot in range(program.num_inputs)
            ]
            run = jax.vmap(run, in_axes=(axes,))
        split_args = batched is not None and donate
        if split_args:
            # (stacked, shared) so that only the stacked slots donate
            stacked_slots = sorted(batched)
            shared_slots = [
                s for s in range(program.num_inputs) if s not in batched
            ]
            run_merged = run

            def run(stacked, shared):
                buffers = [None] * program.num_inputs
                for slot, buf in zip(stacked_slots, stacked):
                    buffers[slot] = buf
                for slot, buf in zip(shared_slots, shared):
                    buffers[slot] = buf
                return run_merged(buffers)

        module = "tnc_program" if batched is None else "tnc_program_batched"
        jitted = named_jit(
            run,
            module if role is None else f"tnc_{role}",
            steps=program_step_list(program),
            donate_argnums=(0,) if donate else (),
        )
        n_steps = len(program.steps)
        first_call = [True]  # compile-vs-execute split for the trace

        def fn(buffers, _jitted=jitted):
            # transient runtime failures (preemption notice, ICI/DCN
            # hiccup) retry the dispatch under the shared policy; OOM and
            # genuine errors re-raise for the callers' degradation
            # ladders. The no-failure path costs one extra frame.
            def _dispatch():
                _faults.fault_point("backend.dispatch")
                if split_args:
                    out = _jitted(
                        [buffers[s] for s in stacked_slots],
                        [buffers[s] for s in shared_slots],
                    )
                else:
                    out = _jitted(buffers)
                if _retry.sync_dispatch():
                    # surface async device failures inside this guarded
                    # region instead of at the next use of the result
                    jax.block_until_ready(out)
                return out

            def _run_with_retry():
                # the guard downgrades TRANSIENT to FATAL once a donating
                # dispatch consumed the inputs (retrying deleted arrays
                # would mask the original error)
                return _retry.default_policy().run(
                    _dispatch,
                    label="backend.dispatch",
                    classify=_retry.donation_guarded_classify(buffers),
                )

            with warnings.catch_warnings():
                # Tiny gate inputs routinely can't back larger intermediates;
                # XLA's per-buffer donation warning is pure noise here.
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable"
                )
                if not obs.enabled():
                    first_call[0] = False
                    return _run_with_retry()
                # first call of a traced program pays the XLA compile
                # (jax.jit is lazy); later calls are dispatch-only
                name = (
                    "backend.compile+dispatch"
                    if first_call[0]
                    else "backend.dispatch"
                )
                first_call[0] = False
                with obs.span(name, steps=n_steps):
                    return _run_with_retry()

        # the bare jax.jit, for lowering on ShapeDtypeStructs (a compile
        # for a described device, where nothing can be placed)
        fn.jitted = jitted
        with _PROGRAM_JIT_CACHE_LOCK:
            _PROGRAM_JIT_CACHE[key] = fn
            while len(_PROGRAM_JIT_CACHE) > _PROGRAM_JIT_CACHE_MAX:
                _PROGRAM_JIT_CACHE.popitem(last=False)
    return fn


class ResidentLeaves:
    """Content-addressed LRU of leaf buffers already on a device:
    ``(host shape, host dtype, host bytes, placed dtype, split flag,
    placement target)`` → the buffer (or (real, imag) pair) that content
    was placed as. Keyed by content, never identity: numpy arrays are
    mutable and ``TensorData.into_data()`` builds a fresh array per
    call, so an in-place edit must miss and a rebuilt equal gate must
    hit. Equal leaves of one call share one buffer, which is why a
    stored buffer must never reach a donating executable (see
    :func:`place_buffers`). Host dict operations under a lock only:
    nothing here touches the device or compiles."""

    # leaves above this bypass the store: hashing them costs what the
    # copy does, and one of them would evict every gate tensor
    MAX_LEAF_BYTES = 1 << 20
    # all stored buffers together: 0.4 % of a v5e's 16 GB of HBM
    MAX_TOTAL_BYTES = 64 << 20
    # host bytes up to this are the key themselves; above, their digest
    _INLINE_BYTES = 256

    def __init__(
        self,
        max_total_bytes: int = MAX_TOTAL_BYTES,
        max_leaf_bytes: int = MAX_LEAF_BYTES,
    ):
        self.max_total_bytes = max_total_bytes
        self.max_leaf_bytes = max_leaf_bytes
        self._lock = threading.Lock()
        self._entries: "OrderedDict[tuple, tuple[Any, int]]" = OrderedDict()
        self.total_bytes = 0

    def key(self, array: np.ndarray, placed_dtype: str, split: bool, target):
        """The store key of a host leaf, or None when it bypasses the
        store (over the leaf limit)."""
        if array.nbytes > self.max_leaf_bytes:
            return None
        if array.nbytes <= self._INLINE_BYTES:
            content = array.tobytes()
        else:
            content = hashlib.blake2b(
                np.ascontiguousarray(array), digest_size=16
            ).digest()
        return (
            array.shape, array.dtype.str, content, placed_dtype, split, target
        )

    def get(self, key):
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                return None
            self._entries.move_to_end(key)
            return entry[0]

    def put(self, key, buffer, nbytes: int):
        """Store ``buffer`` under ``key`` and return the buffer to use:
        the one already there when another thread placed the same
        content first. Evicts least-recently-used entries down to the
        bound (dropping the store's reference only: a caller still
        holding an evicted buffer keeps it alive)."""
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                return entry[0]
            self._entries[key] = (buffer, nbytes)
            self.total_bytes += nbytes
            while self.total_bytes > self.max_total_bytes:
                _, (_, evicted) = self._entries.popitem(last=False)
                self.total_bytes -= evicted
            return buffer

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


RESIDENT_LEAVES = ResidentLeaves()


def place_buffers(
    arrays: Sequence[Any],
    dtype,
    split_complex: bool,
    device=None,
    transient: Iterable[int] = (),
) -> list[Any]:
    """Host arrays → device buffers: complex arrays as-is, or (real, imag)
    float pairs in split mode. The one placement rule of
    :class:`JaxBackend`, the chunked executor and the distributed
    executors.

    Each leaf is looked up in :data:`RESIDENT_LEAVES` by content first
    and copied host-to-device only on a miss, so a served batch, a
    sliced call or an SPMD call places its unchanged gate tensors once
    per process, not once per call. ``device`` is the placement target
    and part of the key: a ``Device``, ``None`` (uncommitted, default
    device) or a ``Sharding`` (the SPMD entry's replicated
    ``NamedSharding``).

    ``transient``: slots that get a buffer of their own, neither looked
    up nor stored — the request-dependent slots of a batch, and **every
    slot of a call whose executable donates its inputs**: a stored
    buffer is shared (by equal leaves, by later calls), so it must
    never be donated. Leaves over ``ResidentLeaves.MAX_LEAF_BYTES`` and
    arrays already on a device are transient too.

    The ``backend.place_buffers`` phase carries ``n`` (slots),
    ``placed`` (leaves copied), ``hits`` (leaves found resident) and
    ``bytes`` (bytes copied: resident leaves count nothing)."""
    import jax

    store = RESIDENT_LEAVES
    placed_dtype = (
        ("float64" if "128" in str(dtype) else "float32")
        if split_complex
        else str(np.dtype(dtype))
    )
    if split_complex:
        from tnc_tpu.ops.split_complex import split_array

        def host(a):
            return split_array(a, placed_dtype)

    else:

        def host(a):
            if isinstance(a, jax.Array) and a.dtype == placed_dtype:
                return a  # already on a device: moved, not fetched
            return np.asarray(a, dtype=placed_dtype)

    with obs.phase("backend.place_buffers", n=len(arrays)) as osp:
        transient = frozenset(transient)
        out: list[Any] = [None] * len(arrays)
        pending = []  # (slot, store key or None, host parts) to copy
        for slot, a in enumerate(arrays):
            key = None
            if slot not in transient and not isinstance(a, jax.Array):
                a = np.asarray(a)
                key = store.key(a, placed_dtype, split_complex, device)
                if key is not None:
                    out[slot] = store.get(key)
            if out[slot] is None:
                parts = host(a)
                if key is not None and parts is a:
                    # the CPU backend's device_put may alias host memory:
                    # a stored buffer must not follow the caller's edits
                    parts = parts.copy()
                pending.append((slot, key, parts))
        # one transfer call for everything that has to move
        buffers = jax.device_put([parts for _, _, parts in pending], device)
        nbytes = 0
        for (slot, key, parts), buf in zip(pending, buffers):
            size = sum(p.nbytes for p in parts) if split_complex else parts.nbytes
            nbytes += size
            out[slot] = buf if key is None else store.put(key, buf, size)
        hits = len(arrays) - len(pending)
        osp.add(placed=len(pending), hits=hits, bytes=nbytes)
        obs.counter_add("resident_leaves.hit", hits)
        obs.counter_add("resident_leaves.miss", len(pending))
        return out


class NumpyBackend(Backend):
    name = "numpy"

    def __init__(self, dtype=np.complex128):
        self.dtype = np.dtype(dtype)

    def execute(
        self,
        program: ContractionProgram,
        arrays: Sequence[Any],
        step_spans: bool | None = None,
    ) -> np.ndarray:
        """``step_spans``: per-step timing spans. Default (``None``) —
        on whenever tracing is on (the oracle is synchronous, so the
        timing is exact and costs no sync). Timed regions that must not
        carry span bookkeeping inside them (the bench CPU baseline)
        pass ``False`` explicitly."""
        buffers = [np.asarray(a, dtype=self.dtype) for a in arrays]
        if obs.enabled() and (step_spans is None or step_spans):
            out = run_steps_timed(
                np, program, buffers, float(self.dtype.itemsize)
            )
        else:
            out = _run_steps(np, program, buffers)
        return np.asarray(out).reshape(program.result_shape)

    def execute_batched(
        self,
        program: ContractionProgram,
        arrays: Sequence[Any],
        batched: Sequence[int],
    ) -> np.ndarray:
        """Host counterpart of :meth:`JaxBackend.execute_batched`: the
        slots in ``batched`` carry a leading ``(B, ...)`` axis, every
        other slot is shared. The batch leg is threaded through the
        step list (:mod:`tnc_tpu.ops.batched`) so each touched step
        runs as one stacked GEMM — per-entry results bit-compare to B
        sequential :meth:`execute` calls. Falls back to the sequential
        loop when a step cannot carry the leg. Returns ``(B,) +
        result_shape``. ``batched`` must name at least one slot — with
        none there is no batch axis to thread; use :meth:`execute`."""
        from tnc_tpu.ops.batched import (
            run_steps_batched,
            stacked_rows,
            thread_batch,
        )

        batched = list(batched)
        if not batched:
            raise ValueError(
                "execute_batched needs at least one batched slot; "
                "use execute() for unbatched programs"
            )
        b = int(np.asarray(arrays[batched[0]]).shape[0])
        flags, threadable = thread_batch(program, batched)
        if threadable:
            buffers = [np.asarray(a, dtype=self.dtype) for a in arrays]
            out = run_steps_batched(np, program, buffers, flags)
            return np.asarray(out).reshape((b,) + tuple(program.result_shape))
        return stacked_rows(
            lambda per: self.execute(program, per),
            list(arrays), batched, b, program.result_shape,
        )

    supports_slice_hooks = True

    def execute_sliced(
        self,
        sp,
        arrays: Sequence[Any],
        max_slices: int | None = None,
        host: bool = True,
        hoist: bool | None = None,
        slice_range: tuple[int, int] | None = None,
        ckpt: str | None = None,
        on_slice=None,
    ) -> np.ndarray:
        """``host=False`` mirrors the device backends' contract as far
        as it applies here (data is already host-resident): the result
        comes back in **stored** (merged) shape instead of
        ``result_shape``. ``hoist`` defaults to off — the naive loop
        is the oracle the hoisted executors are tested against.
        ``slice_range=(lo, hi)`` sums only that contiguous slice shard
        (the multi-host serving partial). ``ckpt`` / ``on_slice``
        (``supports_slice_hooks``): slice-boundary checkpointing and
        cooperative preemption — see
        :func:`~tnc_tpu.ops.sliced.execute_sliced_numpy`."""
        from tnc_tpu.ops.sliced import execute_sliced_numpy

        out = execute_sliced_numpy(
            sp, arrays, dtype=self.dtype, max_slices=max_slices,
            hoist=bool(hoist), slice_range=slice_range,
            ckpt=ckpt, on_slice=on_slice,
        )
        if not host:
            return out.reshape(sp.program.stored_result_shape)
        return out


class JaxBackend(Backend):
    """jit-compiled whole-path execution on the default JAX device.

    >>> import numpy as np
    >>> from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor
    >>> from tnc_tpu.tensornetwork.tensordata import TensorData
    >>> from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    >>> from tnc_tpu.ops.program import build_program, flat_leaf_tensors
    >>> tn = CompositeTensor([
    ...     LeafTensor([0], [2], TensorData.matrix(np.array([1.0, 2.0]))),
    ...     LeafTensor([0], [2], TensorData.matrix(np.array([3.0, 4.0])))])
    >>> path = Greedy(OptMethod.GREEDY).find_path(tn).replace_path()
    >>> program = build_program(tn, path)
    >>> arrays = [l.data.into_data() for l in flat_leaf_tensors(tn)]
    >>> complex(JaxBackend(dtype="complex64").execute(program, arrays))
    (11+0j)
    >>> complex(NumpyBackend().execute(program, arrays))
    (11+0j)

    Off-CPU the backend automatically switches to split-complex mode
    (tensors as (real, imag) float pairs, Gauss 3-matmul contractions) —
    the TPU runtime has no complex dtypes (see
    :mod:`tnc_tpu.ops.split_complex`). ``precision`` controls the MXU
    matmul passes in split mode ('default' | 'float32' | 'highest').
    """

    name = "jax"

    def __init__(
        self,
        dtype="complex64",
        donate: bool = True,
        device=None,
        split_complex: bool | None = None,
        precision: str | None = "float32",
        slice_batch: int = 8,
        chunk_steps: int = 64,
        hoist: bool = True,
    ):
        """A sliced program runs through the chunked executor
        (:mod:`tnc_tpu.ops.chunked`): the program split into chunks of
        ``chunk_steps`` steps (K small compiles), ``slice_batch`` slices
        a dispatch, the host loop carrying checkpoints, retries and
        slice ranges.

        ``hoist`` (default True): execute the slice-invariant stem once
        per call and loop only the residual program (see
        :mod:`tnc_tpu.ops.hoist`); degrades to the naive loop when every
        step depends on a sliced leg. Per-call overrides via
        ``execute_sliced(..., hoist=...)``."""
        import jax

        self._jax = jax
        self.dtype = dtype
        self.donate = donate
        self.device = device
        from tnc_tpu.ops.split_complex import interpret_for

        target = device or jax.devices()[0]
        if split_complex is None:
            split_complex = target.platform != "cpu"
        self.split_complex = split_complex
        # Pallas interpret mode follows the device this backend targets,
        # resolved once here and passed down to every kernel — never
        # read from the process
        self.interpret = interpret_for(target)
        self.precision = precision
        self.slice_batch = slice_batch
        self.chunk_steps = chunk_steps
        self.hoist = hoist
        self._policy_cache: dict[tuple, Any] = {}

    def kernel_policy(self, program: ContractionProgram):
        """The kernel promotion ladder for ``program`` (split mode
        only; ``None`` otherwise): per-step naive/gauss/strassen modes
        plus fused multi-step chains, planned once per (program, env
        override) from the live calibrated cost model when one can be
        fitted (:meth:`tnc_tpu.obs.calibrate.CalibratedCostModel.
        from_registry`) and cached — the policy is part of the jit
        key, so it must not flap between calls as new step samples
        arrive."""
        if not self.split_complex:
            return None
        from tnc_tpu.ops.split_complex import (
            complex_mult_key,
            dot_precision_key,
            plan_kernels,
        )

        key = (program.signature(), complex_mult_key(), dot_precision_key())
        policy = self._policy_cache.get(key)
        if policy is None:
            cost_model = None
            try:
                from tnc_tpu.obs.calibrate import CalibratedCostModel

                cost_model = CalibratedCostModel.from_registry()
            except Exception:  # noqa: BLE001 — planning must not fail
                cost_model = None
            policy = plan_kernels(program, cost_model=cost_model)
            self._policy_cache[key] = policy
        return policy

    def _compiled(self, program: ContractionProgram, batched=None, donate=None):
        """The jitted executable of ``program`` under this backend's
        kernel policy (both cached): the look-up a dispatch pays."""
        with obs.phase("backend.lookup"):
            precision = self.precision if self.split_complex else None
            return jit_program(
                program, self.split_complex, precision,
                self.donate if donate is None else donate,
                batched=batched, policy=self.kernel_policy(program),
                interpret=self.interpret,
            )

    def _fetch(self, result, shape) -> np.ndarray:
        """Device result → host complex array of ``shape``: the wait for
        the device, the device-to-host copy and the host combine."""
        with obs.phase("backend.fetch"):
            if self.split_complex:
                from tnc_tpu.ops.split_complex import combine_array

                return combine_array(*result).reshape(shape)
            return np.asarray(result).reshape(shape)

    def _device_buffers(
        self, arrays: Sequence[Any], transient: Iterable[int] = ()
    ) -> list[Any]:
        return place_buffers(
            arrays, self.dtype, self.split_complex, self.device, transient
        )

    def execute(self, program: ContractionProgram, arrays: Sequence[Any]) -> np.ndarray:
        return self._fetch(self._run(program, arrays), program.result_shape)

    def _run(self, program: ContractionProgram, arrays: Sequence[Any]):
        # the executable donates all its inputs when self.donate: none
        # may be a resident leaf then (jit_program's donation rule)
        buffers = self._device_buffers(
            arrays, transient=range(len(arrays)) if self.donate else ()
        )
        if obs.enabled() and obs.step_timing_enabled():
            # TNC_TPU_STEP_TIME: eager op-by-op execution, blocking on
            # each step's result — every step span carries a true
            # measured device time next to its predicted flops/bytes
            # (the calibration input). Orders of magnitude slower than
            # the compiled path; never on by default.
            import jax
            import jax.numpy as jnp

            return run_steps_timed(
                jnp,
                program,
                list(buffers),
                dtype_bytes=dtype_width(self.dtype),
                split_complex=self.split_complex,
                precision=self.precision,
                sync=jax.block_until_ready,
                policy=self.kernel_policy(program),
                interpret=self.interpret,
            )
        fn = self._compiled(program)
        with obs.phase("backend.execute"):
            return fn(buffers)

    def execute_sliced(
        self,
        sp,
        arrays: Sequence[Any],
        max_slices: int | None = None,
        host: bool = True,
        hoist: bool | None = None,
        slice_range: tuple[int, int] | None = None,
    ):
        """Run a sliced program on the device, through the chunked
        executor (:mod:`tnc_tpu.ops.chunked`).
        ``max_slices`` caps the loop (partial sum — benchmark subsets).
        ``host=False`` keeps the result on device in stored shape (a
        (real, imag) pair in split mode) — no device→host transfer, the
        benchmark-timing contract.
        ``hoist`` overrides the backend default (slice-invariant stem
        executed once, residual looped — :mod:`tnc_tpu.ops.hoist`).
        ``slice_range=(lo, hi)`` sums only that contiguous slice shard
        (the multi-host serving partial), with the programs every other
        range runs. An unsliced program is its slice 0: a range that
        does not hold it sums nothing."""
        if hoist is None:
            hoist = self.hoist
        obs.counter_add("backend.execute_sliced_calls")
        if slice_range is not None and max_slices is not None:
            raise ValueError("slice_range and max_slices are exclusive")
        if sp.slicing.num_slices == 1:
            if slice_range is not None and not (
                slice_range[0] <= 0 < slice_range[1]
            ):
                if host:
                    return np.zeros(sp.program.result_shape, dtype=self.dtype)
                stored = np.zeros(
                    sp.program.stored_result_shape, dtype=self.dtype
                )
                return self._device_buffers([stored], transient=(0,))[0]
            if not host:  # device-resident, stored shape — no D2H
                return self.execute_on_device(sp.program, arrays)
            return self.execute(sp.program, arrays)

        from tnc_tpu.ops.chunked import execute_sliced_batched_jax

        return execute_sliced_batched_jax(
            sp,
            arrays,
            batch=self.slice_batch,
            chunk_steps=self.chunk_steps,
            split_complex=self.split_complex,
            precision=self.precision,
            dtype=self.dtype,
            device=self.device,
            max_slices=max_slices,
            host=host,
            hoist=hoist,
            slice_range=None if slice_range is None else tuple(slice_range),
            interpret=self.interpret,
        )

    def execute_batched(
        self,
        program: ContractionProgram,
        arrays: Sequence[Any],
        batched: Sequence[int],
    ) -> np.ndarray:
        """Run ``program`` once over a leading batch axis carried by the
        slots in ``batched`` (their arrays are stacked ``(B, ...)``;
        every other slot is shared). The whole path is ``jax.vmap``-ed
        and jitted once — B network evaluations for one compile and one
        dispatch, the TPU-native shape for amplitude sweeps
        (:mod:`tnc_tpu.tensornetwork.sweep`). Returns ``(B,) +
        result_shape``."""
        fn = self._compiled(program, batched=frozenset(batched))
        buffers = self._device_buffers(arrays, transient=batched)
        with obs.phase("backend.execute"):
            result = fn(buffers)
        return self._fetch(result, (-1,) + tuple(program.result_shape))

    def execute_on_device(self, program: ContractionProgram, arrays: Sequence[Any]):
        """Like :meth:`execute` but leaves the result on device (no host
        round-trip; a (real, imag) pair in split mode) — used for
        benchmarking and distributed fan-in. The buffer is in **stored**
        shape (``program.stored_result_shape``) with axes in
        ``program.result_legs`` order, not ``result_shape``/canonical
        order — reshape/permute host-side when leg semantics matter.
        """
        return self._run(program, arrays)

    def bind_resident(self, program: ContractionProgram, arrays: Sequence[Any]):
        """Stage ``arrays`` to the device once and return a zero-transfer
        callable: each call re-dispatches the compiled program on the
        resident input buffers and returns the device-resident result
        (stored shape; a (real, imag) pair in split mode).

        Donation is disabled for the bound executable so the resident
        inputs survive arbitrarily many calls. Since
        :func:`place_buffers` keeps unchanged leaves resident for every
        caller, what this still adds is the closure: no content look-up
        and no executable look-up per call, for timing a bare dispatch.
        Only ``bench.py`` needs that (ROADMAP C1).
        """
        fn = self._compiled(program, donate=False)
        buffers = self._device_buffers(arrays)
        return lambda: fn(buffers)


_BACKENDS: dict[str, Backend] = {}


def get_backend(name: str | Backend | None = None) -> Backend:
    """Resolve a backend by name ('numpy', 'jax'), instance, or default."""
    if isinstance(name, Backend):
        return name
    if name is None:
        name = "numpy"
    backend = _BACKENDS.get(name)
    if backend is None:
        if name == "numpy":
            backend = NumpyBackend()
        elif name == "jax":
            backend = JaxBackend()
        elif name == "jax64":
            backend = JaxBackend(dtype="complex128")
        else:
            raise ValueError(f"Unknown backend '{name}'")
        _BACKENDS[name] = backend
    return backend
