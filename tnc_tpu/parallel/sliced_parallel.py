"""Slice-parallel distributed contraction over a device mesh.

The reference parallelizes by graph partitioning + MPI fan-in
(``tnc/src/mpi/communication.rs``). On a TPU mesh, the natural first axis
of parallelism is different: **slices**. A sliced contraction is a sum of
``num_slices`` identical-shape programs — perfectly SPMD. Each device
executes its chunk of the slice range with the same compiled program and
a single ``psum`` over the mesh combines the partial sums on ICI.

This composes with partition parallelism (``tnc_tpu.parallel.partitioned``)
the way data parallelism composes with model parallelism in ML stacks.

Works on any ``jax.sharding.Mesh`` — real TPU ICI or the virtual CPU
device count used in tests (the ``mpi_test`` analogue).
"""

from __future__ import annotations

import logging
import numpy as np

logger = logging.getLogger(__name__)

from tnc_tpu import obs
from tnc_tpu.contractionpath.contraction_path import ContractionPath
from tnc_tpu.contractionpath.slicing import Slicing
from tnc_tpu.ops.backends import named_jit, place_buffers
from tnc_tpu.resilience import faultinject as _faults
from tnc_tpu.resilience import retry as _retry
from tnc_tpu.ops.program import flat_leaf_tensors
from tnc_tpu.ops.sliced import (
    SlicedProgram,
    build_sliced_program,
    program_slice_fn,
)
from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor
from tnc_tpu.tensornetwork.tensordata import TensorData


def _effective_chunk(
    num_slices: int, n_devices: int, max_slices: int | None
) -> int:
    """Per-device slice count actually executed: the full share, shrunk
    to ``ceil(max_slices / n_devices)`` under a probe subset. The ONE
    definition shared by the compiled fn, its cache key, and the trace
    flop accounting — they must never disagree on the chunk size."""
    chunk = num_slices // n_devices
    if max_slices is not None:
        chunk = min(chunk, max(1, -(-max_slices // n_devices)))
    return chunk


def make_mesh(n_devices: int | None = None, axis: str = "slices"):
    """Build a 1-D mesh over the first ``n_devices`` JAX devices."""
    import jax
    from jax.sharding import Mesh

    devices = jax.devices()
    if n_devices is not None:
        if len(devices) < n_devices:
            raise ValueError(
                f"need {n_devices} devices, have {len(devices)} "
                f"({devices[0].platform})"
            )
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (axis,))


def _make_spmd_fn(
    sp: SlicedProgram,
    mesh,
    axis: str,
    dtype,
    split_complex: bool,
    precision: str | None = "float32",
    max_slices: int | None = None,
    hoist: bool = False,
):
    """fn(full_buffers) replicated over the mesh; each device sums its
    slice chunk in a ``fori_loop`` over the per-slice body
    (:func:`tnc_tpu.ops.sliced.slice_body`), then one psum over the
    mesh axis. On a mesh of one device this is the whole slice loop in
    one program.

    ``max_slices`` probe subsets: each device's chunk shrinks to
    ``ceil(max_slices / n_devices)`` and device ``d`` covers slice ids
    ``[d*chunk, (d+1)*chunk)`` of the *shrunk* chunk — i.e. the probe
    is a partial sum over the **first** ``n_devices *
    ceil(max_slices/n_devices)`` slices globally (a contiguous prefix,
    directly comparable against oracle prefix sums), not a subset of
    each device's full-run range.

    ``hoist=True`` traces the slice-invariant prelude once per device
    before its slice loop (:mod:`tnc_tpu.ops.hoist`); the cached
    intermediates are loop constants in each device's HBM and only the
    residual program runs per slice."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    n_devices = mesh.shape[axis]
    num = sp.slicing.num_slices
    if num % n_devices != 0:
        raise ValueError(
            f"num_slices ({num}) must be divisible by mesh size ({n_devices})"
        )
    chunk = _effective_chunk(num, n_devices, max_slices)
    if n_devices * chunk > 1 << 31:
        # the loop's slice id `my * chunk + k` is an int32 on the device
        raise ValueError(
            f"{n_devices * chunk} slices: on-device slice ids are exact "
            "below 2^31; run a range of such a plan through the chunked "
            "executor's slice_range"
        )

    hp = None
    if hoist:
        from tnc_tpu.ops.hoist import hoist_sliced_program

        cand = hoist_sliced_program(sp)
        if not cand.is_noop:
            hp = cand
    loop_sp = hp.residual if hp is not None else sp

    from tnc_tpu.obs import op_table
    from tnc_tpu.ops.backends import program_step_list
    from tnc_tpu.ops.hoist import prelude_step_list
    from tnc_tpu.ops.split_complex import interpret_for, plan_kernels

    # the module's step list: the prelude's steps, once a dispatch, then
    # the loop's, once a slice; a step's scope carries its index in it
    module_steps = prelude_step_list(hp) if hp is not None else []
    first_row = len(module_steps)
    module_steps += program_step_list(loop_sp.program, "row")

    # Pallas interpret mode follows the mesh's devices, not the process
    interpret = interpret_for(mesh.devices.flat[0])
    one_slice = program_slice_fn(
        jnp, loop_sp, split_complex=split_complex, precision=precision,
        # the kernel ladder, planned over the whole residual
        policy=plan_kernels(loop_sp.program) if split_complex else None,
        interpret=interpret,
        numbers=range(first_row, len(module_steps)),
    )
    part_dtype = "float64" if "128" in str(dtype) else "float32"

    def zeros():
        def z():
            return jnp.zeros(
                sp.program.stored_result_shape,
                dtype=part_dtype if split_complex else dtype,
            )

        return (z(), z()) if split_complex else z()

    def device_fn(*full_buffers):
        my = lax.axis_index(axis)
        if hp is not None:
            # invariant prelude: traced once per device, outside the
            # slice loop — its outputs are loop constants in HBM
            from tnc_tpu.ops.hoist import run_prelude

            loop_buffers = run_prelude(
                jnp, hp, list(full_buffers), split_complex, precision,
                interpret,
            )
        else:
            loop_buffers = full_buffers

        def add_slice(k, acc):
            contribution = one_slice(loop_buffers, my * chunk + k)
            with op_table.named_scope(op_table.SLICE_SUM):
                return jax.tree.map(jnp.add, acc, contribution)

        with op_table.named_scope(op_table.SLICE_SUM):
            zero = zeros()
        partial = lax.fori_loop(0, chunk, add_slice, zero)
        with op_table.named_scope(op_table.SLICE_SUM):
            return lax.psum(partial, axis)

    in_specs = tuple(P() for _ in range(sp.program.num_inputs))  # replicated
    # check_vma off: the psum inside the body trips the strict
    # replication checker
    fn = jax.shard_map(
        device_fn, mesh=mesh, in_specs=in_specs, out_specs=P(),
        check_vma=False,
    )
    from jax.sharding import NamedSharding

    return named_jit(
        fn, "tnc_spmd_slices", steps=module_steps,
        # the leaves arrive committed and replicated over the mesh
        sharding=NamedSharding(mesh, P()),
    )


# Executable cache: _make_spmd_fn builds a fresh closure per call, so
# jax.jit alone can never dedupe — without this, a benchmark's timed
# call after a warmup at the SAME chunk would re-trace and re-compile
# inside the timed region (r5 review finding).
_SPMD_FN_CACHE: dict = {}
_SPMD_FN_CACHE_MAX = 64


def _spmd_fn_cached(sp, mesh, axis, dtype, split_complex, precision,
                    max_slices, hoist=False):
    from tnc_tpu.ops.split_complex import complex_mult_key, dot_precision_key

    n_devices = mesh.shape[axis]
    chunk = _effective_chunk(sp.slicing.num_slices, n_devices, max_slices)
    key = (
        sp.signature(), tuple(mesh.devices.flat), axis, str(dtype),
        split_complex, precision, chunk, hoist,
        # the split trace bakes in the kernel policy/env mode — a stale
        # fn under a flipped TNC_TPU_COMPLEX_MULT (or a flipped
        # TNC_TPU_DOT_PRECISION rung) would silently run the wrong
        # kernels
        complex_mult_key() if split_complex else None,
        dot_precision_key() if split_complex else None,
    )
    fn = _SPMD_FN_CACHE.get(key)
    obs.counter_add("spmd_fn_cache.hit" if fn is not None else
                    "spmd_fn_cache.miss")
    if fn is None:
        fn = _make_spmd_fn(
            sp, mesh, axis, dtype, split_complex, precision, max_slices,
            hoist,
        )
        _SPMD_FN_CACHE[key] = fn
        while len(_SPMD_FN_CACHE) > _SPMD_FN_CACHE_MAX:
            _SPMD_FN_CACHE.pop(next(iter(_SPMD_FN_CACHE)))
    return fn


def distributed_sliced_contraction(
    tn: CompositeTensor,
    contract_path: ContractionPath,
    slicing: Slicing,
    mesh=None,
    n_devices: int | None = None,
    dtype: str = "complex64",
    axis: str = "slices",
    split_complex: bool | None = None,
    precision: str | None = "float32",
    max_slices: int | None = None,
    hoist: bool = False,
) -> LeafTensor:
    """Contract ``tn`` with slices distributed over a device mesh.

    ``max_slices``: probe subsets — the partial sum over the **first**
    ``n_devices * ceil(max_slices / n_devices)`` slices globally (each
    device covers a contiguous range of that prefix; see
    :func:`_make_spmd_fn`).

    ``hoist=True``: each device computes the slice-invariant prelude
    once before its slice loop and iterates only the residual program
    (:mod:`tnc_tpu.ops.hoist`).

    Every device holds the (replicated, small) leaf tensors, runs the same
    compiled per-slice program over its chunk of the slice range, and the
    partial sums reduce with one ``psum`` on ICI. Split-complex mode is
    selected automatically off-CPU (the TPU runtime has no complex
    dtypes).

    >>> import numpy as np
    >>> from tnc_tpu.contractionpath.contraction_path import ContractionPath
    >>> from tnc_tpu.contractionpath.slicing import find_slicing
    >>> from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor
    >>> from tnc_tpu.tensornetwork.tensordata import TensorData
    >>> rng = np.random.default_rng(0)
    >>> ts = [LeafTensor([0, 1], [4, 4], TensorData.matrix(rng.standard_normal((4, 4)))),
    ...       LeafTensor([1, 2], [4, 4], TensorData.matrix(rng.standard_normal((4, 4)))),
    ...       LeafTensor([2, 0], [4, 4], TensorData.matrix(rng.standard_normal((4, 4))))]
    >>> tn = CompositeTensor([t.copy() for t in ts])
    >>> path = ContractionPath.simple([(0, 1), (0, 2)])
    >>> slicing = find_slicing(ts, path.toplevel, target_size=12)
    >>> out = distributed_sliced_contraction(tn, path, slicing, n_devices=1)
    >>> a, b, c = (t.data.into_data() for t in ts)
    >>> want = np.einsum("ab,bc,ca->", a, b, c)
    >>> bool(abs(complex(out.data.into_data().reshape(-1)[0]) - want)
    ...      <= 1e-5 * abs(want))
    True
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    if mesh is None:
        mesh = make_mesh(n_devices, axis)
    if split_complex is None:
        split_complex = jax.devices()[0].platform != "cpu"

    n_dev = mesh.shape[axis]
    chunk = _effective_chunk(slicing.num_slices, n_dev, max_slices)
    executed = chunk * n_dev  # prefix-subset semantics (_make_spmd_fn)
    # the caller's own jax.profiler session sees these spans beside the
    # device's ops; they record the host-side wall time either way
    with obs.span(
        "spmd.contract", slices=executed, devices=n_dev
    ) as osp:
        with obs.span("spmd.build"):
            sp = build_sliced_program(tn, contract_path, slicing)
            leaves = flat_leaf_tensors(tn)
            logger.debug(
                "sliced SPMD: %d slices over %d devices (%d sliced legs, "
                "split_complex=%s)",
                slicing.num_slices,
                n_dev,
                len(slicing.legs),
                split_complex,
            )
            fn = _spmd_fn_cached(
                sp, mesh, axis, dtype, split_complex, precision, max_slices,
                hoist,
            )
            # the SAME effective-hoist decision _make_spmd_fn takes (the
            # pass is lru-cached, so this re-derivation is a dict hit),
            # so the span's hoisted flag and flop count describe what
            # actually executes
            hp = None
            if hoist:
                from tnc_tpu.ops.hoist import hoist_sliced_program

                cand = hoist_sliced_program(sp)
                if not cand.is_noop:
                    hp = cand
            osp.set(hoisted=hp is not None)
        with obs.span("spmd.place", n=len(leaves)):
            # committed and replicated over the mesh once per content:
            # shard_map's in_specs=P() then finds every leaf in place
            arrays = place_buffers(
                [leaf.data.into_data() for leaf in leaves], dtype,
                split_complex, NamedSharding(mesh, PartitionSpec()),
            )

        def _dispatch():
            _faults.fault_point("spmd.dispatch")
            out = fn(*arrays)
            if _retry.sync_dispatch():
                jax.block_until_ready(out)
            return out

        # transient runtime failures (preemption notice on one chip, ICI
        # hiccup) retry the whole SPMD dispatch under the shared policy —
        # the computation is replicated-input + psum, so a re-dispatch is
        # exact; OOM propagates to the caller's degradation ladder
        with obs.span("spmd.execute"):
            out = _retry.retry_call(_dispatch, label="spmd.dispatch")
        with obs.span("spmd.fetch"):
            if split_complex:
                from tnc_tpu.ops.split_complex import combine_array

                result = combine_array(*out)
            else:
                result = np.asarray(out)
            result = result.reshape(sp.program.result_shape)
        if obs.enabled():
            from tnc_tpu.ops.program import steps_flops

            if hp is not None:
                # hoisted: each device runs the prelude once, then the
                # residual per slice of its chunk
                flops = n_dev * steps_flops(
                    ps.step for ps in hp.prelude_steps
                ) + executed * steps_flops(hp.residual.program.steps)
            else:
                flops = executed * steps_flops(sp.program.steps)
            osp.add(flops=flops)
    return LeafTensor(
        list(sp.program.result_legs),
        list(sp.program.result_shape),
        TensorData.matrix(result),
    )
