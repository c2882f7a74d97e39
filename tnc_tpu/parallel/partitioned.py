"""Partition-parallel distributed contraction over JAX devices.

TPU-native equivalent of the reference's MPI runtime
(``tnc/src/mpi/communication.rs``). The reference's pipeline is

    rank 0: partition → per-partition paths → toplevel fan-in path
    broadcast_path / scatter_tensor_network    (bcast + p2p sends)
    every rank: contract its partition locally (zero communication)
    intermediate_reduce_tensor_network         (pairwise p2p fan-in)

Here the same schedule runs under JAX's single-controller model:

- *Scatter* = ``jax.device_put`` of each partition's leaf tensors onto its
  device. No serialization layer is needed (the reference needs postcard +
  192-byte MPI blobs, ``mpi/serialization.rs``, ``mpi_types.rs:73-83``);
  arrays move host→HBM directly.
- *Local phase* = each partition's whole nested path compiled to one XLA
  program and dispatched to its device. JAX dispatch is asynchronous, so
  all devices compute their partitions **concurrently** — the analogue of
  the independent per-rank contraction phase.
- *Fan-in reduce* = the ``toplevel`` path interpreted as a communication
  schedule, exactly like ``intermediate_reduce_tensor_network``
  (``communication.rs:199-249``): for each pair ``(x, y)`` the tensor held
  by ``y``'s device is ``device_put`` onto ``x``'s device (a direct
  device-to-device copy — ICI on a TPU slice) and contracted there.
- *Final tensor on device 0*: ``DeviceTensorMapping`` assigns the
  partition that survives the fan-in to device 0, mirroring
  ``get_tensor_mapping`` reserving rank 0 (``communication.rs:89-115``).

Multi-host scaling: under ``jax.distributed.initialize`` the same code
addresses every device in the pod; ``device_put`` between hosts rides
DCN. There is no rank-local control flow to port.
"""

from __future__ import annotations

import contextlib
import logging
import time
from dataclasses import dataclass
from typing import Any, Sequence

import numpy as np

logger = logging.getLogger(__name__)

from tnc_tpu import obs
from tnc_tpu.contractionpath.contraction_path import ContractionPath
from tnc_tpu.ops.backends import jit_program, place_buffers
from tnc_tpu.ops.program import (
    ContractionProgram,
    _pair_step,
    build_program,
    step_flops,
)
from tnc_tpu.resilience import faultinject as _faults
from tnc_tpu.resilience import retry as _retry
from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor
from tnc_tpu.tensornetwork.tensordata import TensorData


def _process_index() -> int:
    """This host's jax process index (0 when jax is not initialized —
    error paths must not fail while naming a failure)."""
    try:
        import jax

        return int(jax.process_index())
    except Exception:  # noqa: BLE001 — never raise from error naming
        return 0


class PartitionExecutionError(RuntimeError):
    """A partition's scatter, local contraction, or fan-in step failed;
    names the partition index, device slot, and **host process** so a
    pool-surfaced XLA error in a multi-host incident log is attributable
    to a machine (``pool.map`` otherwise raises a bare runtime error
    with no hint of which partition — let alone which host — died).
    Chains the original (``__cause__``)."""

    def __init__(
        self,
        partition: int,
        device: int,
        original: BaseException,
        process: int | None = None,
        phase: str = "local",
    ):
        if process is None:
            process = _process_index()
        super().__init__(
            f"partition {partition} on device {device} "
            f"(process {process}, {phase} phase) failed: "
            f"{type(original).__name__}: {original}"
        )
        self.partition = partition
        self.device = device
        self.process = process
        self.phase = phase
        self.original = original

def partition_latency_map(
    tn: CompositeTensor,
    contract_path: ContractionPath,
    cost_model=None,
) -> dict[int, float]:
    """Per-partition local completion latencies for fan-in scheduling —
    never ``None``-filled: predicted seconds under ``cost_model`` (a
    :class:`~tnc_tpu.obs.calibrate.CalibratedCostModel`, dispatch
    overhead charged per local step), raw local op counts otherwise.

    This is what the latency-aware communication schemes
    (``WEIGHTED_BRANCH_BOUND``, ``BIPARTITION_SWEEP``) must receive on
    the partitioned path: with an empty latency map every partition
    looks instantly available and the "latency-aware" schedule
    degenerates to a plain flops fan-in.
    """
    from tnc_tpu.contractionpath.contraction_cost import contract_path_cost

    latency: dict[int, float] = {}
    steps: dict[int, float] = {}
    for i, child in enumerate(tn.tensors):
        if not isinstance(child, CompositeTensor):
            raise TypeError(f"top-level child {i} is not a partition composite")
        if i not in contract_path.nested:
            raise ValueError(f"partition {i} has no nested contraction path")
        local = contract_path.nested[i]
        flops, _ = contract_path_cost(child.tensors, local, True)
        latency[i] = flops
        steps[i] = float(len(local.toplevel))
    if cost_model is not None:
        from tnc_tpu.contractionpath.communication_schemes import (
            calibrated_latency_map,
        )

        latency = calibrated_latency_map(latency, cost_model, steps)
    return latency


def replan_fanin(
    tn: CompositeTensor,
    contract_path: ContractionPath,
    communication_scheme,
    cost_model=None,
    rng=None,
) -> ContractionPath:
    """Re-derive the toplevel fan-in schedule of a partitioned path with
    a latency-aware communication scheme, keeping the nested local
    paths. The latency map comes from :func:`partition_latency_map` —
    calibrated seconds when a ``cost_model`` is given — so deferring a
    slow partition's tensor is priced against real completion times.
    """
    import random as _random

    latency = partition_latency_map(tn, contract_path, cost_model)
    children = [
        child.external_tensor() for child in tn.tensors
    ]  # type: ignore[union-attr]
    toplevel = communication_scheme.communication_path(
        children,
        latency,
        rng if rng is not None else _random.Random(42),
        cost_model=cost_model,
    )
    return ContractionPath(dict(contract_path.nested), list(toplevel))


def _fanin_survivor(k: int, toplevel: Sequence[tuple[int, int]]) -> int:
    """Index that holds the final tensor after a replace-left fan-in."""
    alive = [True] * k
    for x, y in toplevel:
        if not (alive[x] and alive[y]):
            raise ValueError(f"communication path reuses a consumed index: {(x, y)}")
        alive[y] = False
    survivors = [i for i, a in enumerate(alive) if a]
    if len(survivors) != 1:
        raise ValueError(
            f"communication path leaves {len(survivors)} tensors, expected 1"
        )
    return survivors[0]


@dataclass(frozen=True)
class DeviceTensorMapping:
    """Partition index ↔ device, final-result partition pinned to device 0.

    Equivalent of ``RankTensorMapping`` (``mpi/mpi_types.rs:11-62``) +
    ``get_tensor_mapping`` (``communication.rs:89-115``).
    """

    device_of_partition: tuple[int, ...]  # partition i → device slot

    @classmethod
    def for_path(
        cls, k: int, toplevel: Sequence[tuple[int, int]]
    ) -> "DeviceTensorMapping":
        root = _fanin_survivor(k, toplevel)
        order = [root] + [i for i in range(k) if i != root]
        device_of = [0] * k
        for slot, part in enumerate(order):
            device_of[part] = slot
        return cls(tuple(device_of))

    def device(self, partition: int) -> int:
        return self.device_of_partition[partition]


@dataclass
class Communication:
    """Executor state for one distributed contraction (cf. ``Communication``
    in ``communication.rs:118-122``).

    ``programs[i]`` is either a :class:`ContractionProgram` (partition
    fits HBM) or a :class:`~tnc_tpu.ops.sliced.SlicedProgram` (partition
    sliced to fit — the slicing × partitioning composition the reference
    lists as future work, ``book/src/future_work.md`` item 2)."""

    mapping: DeviceTensorMapping
    devices: list
    programs: list[Any]
    results_meta: list[LeafTensor]


def _pair_program(ta: LeafTensor, tb: LeafTensor) -> tuple[ContractionProgram, LeafTensor]:
    step, result = _pair_step(0, 1, ta, tb)
    program = ContractionProgram(
        num_inputs=2,
        steps=(step,),
        result_slot=0,
        result_legs=tuple(result.legs),
        result_shape=tuple(result.bond_dims),
    )
    return program, result


def _place_partition(child: CompositeTensor, dtype, split_complex: bool, device):
    """One partition's leaves on its device, through the store of
    resident leaves (:func:`~tnc_tpu.ops.backends.place_buffers`): a
    call that finds them there copies nothing host to device. No local
    program donates its inputs (gate leaves could back no intermediate
    anyway), so a stored buffer is never consumed."""
    from tnc_tpu.ops.program import flat_leaf_tensors

    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(child)]
    return place_buffers(arrays, dtype, split_complex, device)


def _partition_program(
    child: CompositeTensor, nested: ContractionPath, hbm_bytes: int
) -> tuple[Any, ContractionProgram]:
    """One partition's local path as ``(entry, program)``: ``entry`` is
    what the local phase runs — the partition's
    :class:`ContractionProgram`, or a
    :class:`~tnc_tpu.ops.sliced.SlicedProgram` where that does not fit
    ``hbm_bytes`` — and ``program`` the contraction program either way
    (the fan-in's metadata comes from its result)."""
    program = build_program(child, nested)
    sp = _slice_partition(child, nested, program, hbm_bytes)
    if sp is not None:
        return sp, sp.program
    return program, program


def _entry_cmacs(entry: Any) -> float:
    """Predicted complex multiply-adds of one partition's local phase
    (all slices of a sliced partition)."""
    from tnc_tpu.ops.sliced import SlicedProgram

    if isinstance(entry, SlicedProgram):
        return entry.slicing.num_slices * sum(
            step_flops(st) for st in entry.program.steps
        )
    return sum(step_flops(st) for st in entry.steps)


def _slice_partition(
    child: CompositeTensor,
    nested: ContractionPath,
    program: ContractionProgram,
    hbm_bytes: int,
):
    """Slice one partition's local path until its program fits the HBM
    budget. Returns a SlicedProgram (or None if the unsliced ``program``
    already fits, or nothing local slicing can do).

    Uses slice-and-reconfigure (slicing interleaved with subtree
    re-planning in the sliced size model) rather than plain greedy leg
    picking: a fixed path's peak is often pinned by a single badly-
    ordered step that reconfiguration dissolves once the sliced legs
    have dim 1. The returned ``SlicedProgram``'s program may therefore
    follow a DIFFERENT (better) local path than ``nested`` — downstream
    fan-in metadata must come from ``sp.program.result_legs`` (it does:
    ``scatter_partitions`` builds metas from the program).
    """
    from tnc_tpu.contractionpath.contraction_path import replace_ssa_ordering
    from tnc_tpu.contractionpath.slicing import slice_and_reconfigure
    from tnc_tpu.ops.budget import fits_hbm, program_peak_bytes
    from tnc_tpu.ops.sliced import build_sliced_program

    if fits_hbm(program, hbm_bytes=hbm_bytes):
        return None
    if nested.nested:
        raise ValueError(
            "HBM budget exceeded on a partition with a nested local path; "
            "slicing supports flat partition paths"
        )
    inputs = [t for t in child.tensors if isinstance(t, LeafTensor)]
    est = program_peak_bytes(program)
    ssa = replace_ssa_ordering(nested.toplevel, len(inputs))
    # element targets, descending from a quarter of the current peak
    # (~8 bytes per complex element; starting AT the peak would be a
    # no-op): first slicing that fits the budget wins; keep the deepest
    # achievable as best effort. A partition whose peak is its own
    # open-leg output cannot be sliced locally at all — only GLOBAL
    # slicing (cut legs sliceable) helps there.
    target = 2.0 ** np.floor(np.log2(max(est.peak_bytes / 8.0 / 4.0, 2.0)))
    best = None
    while target >= 4:
        try:
            pairs, slicing = slice_and_reconfigure(
                inputs, ssa, target,
                reconf_rounds=1, step_budget=None,
                final_rounds=2, final_budget=None,
            )
        except ValueError:
            break
        if not slicing.legs:  # target above the current peak: no-op
            target /= 4.0
            continue
        sp = build_sliced_program(child, ContractionPath.simple(pairs), slicing)
        best = sp
        if fits_hbm(sp.program, hbm_bytes=hbm_bytes):
            break
        target /= 4.0
    if best is None:
        # nothing sliceable (open-leg-bound peak): run unsliced rather
        # than wrap a fake 1-slice program as success
        logger.warning(
            "partition peak %.3g bytes exceeds the %d-byte budget but has "
            "no sliceable (closed) legs; running unsliced — use global "
            "slicing (partitioned_sliced_executor) to slice cut legs",
            est.peak_bytes,
            hbm_bytes,
        )
        return None
    if not fits_hbm(best.program, hbm_bytes=hbm_bytes):
        logger.warning(
            "partition sliced best-effort (%d legs, %d slices) but still "
            "exceeds the %d-byte budget",
            len(best.slicing.legs),
            best.slicing.num_slices,
            hbm_bytes,
        )
    logger.debug(
        "partition sliced: %d legs, %d slices",
        len(best.slicing.legs),
        best.slicing.num_slices,
    )
    return best


def scatter_partitions(
    tn: CompositeTensor,
    contract_path: ContractionPath,
    devices: list,
    dtype: str,
    split_complex: bool,
    hbm_bytes: int | None = None,
) -> tuple[Communication, list[list[Any]]]:
    """Compile per-partition programs and place each partition's leaves on
    its device (``scatter_tensor_network``, ``communication.rs:125-195``).

    Any partition whose program exceeds the per-device budget
    ``hbm_bytes`` (default: what the first device reports,
    :func:`~tnc_tpu.ops.budget.device_hbm_bytes`) is sliced locally
    (sum over slice programs on its own device) before the fan-in —
    composing partition parallelism with slicing.
    """
    children = list(tn.tensors)
    k = len(children)
    for i, child in enumerate(children):
        if not isinstance(child, CompositeTensor):
            raise TypeError(f"top-level child {i} is not a partition composite")
        if i not in contract_path.nested:
            raise ValueError(f"partition {i} has no nested contraction path")
    if k > len(devices):
        raise ValueError(f"{k} partitions but only {len(devices)} devices")

    mapping = DeviceTensorMapping.for_path(k, contract_path.toplevel)
    if hbm_bytes is None:
        from tnc_tpu.ops.budget import device_hbm_bytes

        hbm_bytes = device_hbm_bytes(devices[0])

    programs: list[Any] = []
    metas: list[LeafTensor] = []
    buffers: list[list[Any]] = []
    with obs.phase("partitioned.scatter", partitions=k):
        for i, child in enumerate(children):
            try:
                entry, program = _partition_program(
                    child, contract_path.nested[i], hbm_bytes
                )
                programs.append(entry)
                metas.append(
                    LeafTensor(
                        list(program.result_legs), list(program.result_shape)
                    )
                )
                buffers.append(
                    _place_partition(
                        child, dtype, split_complex,
                        devices[mapping.device(i)],
                    )
                )
            except (ValueError, TypeError):
                raise  # caller contract errors keep their type
            except Exception as exc:  # noqa: BLE001 — name the failure site
                raise PartitionExecutionError(
                    i, mapping.device(i), exc, phase="scatter"
                ) from exc
            # mirror of "Scattering tensor network" (communication.rs:132)
            logger.debug(
                "scatter: partition %d -> device %d (%d tensors, %d steps%s)",
                i,
                mapping.device(i),
                len(child),
                len(program.steps),
                ", sliced" if entry is not program else "",
            )

    comm = Communication(mapping, list(devices), programs, metas)
    return comm, buffers


def local_contract_partitions(
    comm: Communication,
    buffers: list[list[Any]],
    split_complex: bool,
    precision,
    max_slices: int | None = None,
    sliced_strategy: str = "chunked",
    dtype: str = "complex64",
    slice_batch: int = 8,
    chunk_steps: int = 64,
    hoist: bool = False,
) -> list[Any]:
    """Dispatch every partition's compiled program to its device. Async
    dispatch → all devices run concurrently (the per-rank local phase).
    ``max_slices`` caps sliced partitions' loops (benchmark subset mode —
    the partial sums are NOT the correct partition tensors).
    ``hoist=True`` runs each sliced partition's slice-invariant stem
    once before its slice loop (:mod:`tnc_tpu.ops.hoist`).

    Sliced partitions run through the chunked executor by default;
    each partition's buffers are committed to its device, so the
    per-partition chunk dispatches execute there and the k local phases
    still overlap.

    First-run XLA compiles are driven from a thread pool: k distinct
    partition programs would otherwise compile back-to-back on the main
    thread (XLA compilation releases the GIL), serializing exactly the
    phase that should overlap. Warm runs take the sequential fast path.

    ``sliced_strategy="mesh"``: a locally sliced partition's slice
    partial sums reduce with an **on-device collective** (``psum`` over
    a sub-mesh axis) instead of the chunked executor's host
    accumulation loop — partials stay device-resident end to end, and
    devices beyond the partition count (``comm.devices[k:]``) are
    farmed out to the sliced partitions, each of which runs its slice
    range SPMD over its sub-mesh (``tnc_tpu.parallel.sliced_parallel``
    machinery; the sub-mesh shrinks to the largest size dividing the
    partition's slice count).
    """
    if sliced_strategy not in ("chunked", "mesh"):
        raise ValueError(
            f"unknown sliced_strategy {sliced_strategy!r}; "
            "expected 'chunked' or 'mesh'"
        )
    logger.debug("local phase: %d partition programs", len(comm.programs))
    from tnc_tpu.ops.chunked import run_sliced_chunked_placed
    from tnc_tpu.ops.sliced import SlicedProgram
    from tnc_tpu.ops.split_complex import interpret_for

    interpret = interpret_for(comm.devices[0])

    # mesh strategy: hand the spare devices (slots beyond the partition
    # count) to the sliced partitions, round-robin
    spare_of: dict[int, list] = {}
    if sliced_strategy == "mesh":
        k = len(comm.programs)
        sliced_parts = [
            i for i, p in enumerate(comm.programs)
            if isinstance(p, SlicedProgram)
        ]
        spare_of = {i: [] for i in sliced_parts}
        for j, dev in enumerate(comm.devices[k:]):
            if sliced_parts:
                spare_of[sliced_parts[j % len(sliced_parts)]].append(dev)

    def _mesh_fn(i, program):
        import numpy as _np
        from jax.sharding import Mesh

        from tnc_tpu.parallel.sliced_parallel import _spmd_fn_cached

        own = comm.devices[comm.mapping.device(i)]
        sub = [own] + spare_of.get(i, [])
        n = len(sub)
        while program.slicing.num_slices % n:
            n -= 1
        submesh = Mesh(_np.asarray(sub[:n]), ("slices",))
        fn = _spmd_fn_cached(
            program, submesh, "slices", dtype, split_complex, precision,
            max_slices, hoist,
        )

        def run(bufs, _fn=fn, _own=own):
            import jax

            # the SPMD fn replicates its inputs over the sub-mesh
            # itself; feed host copies so single-device-committed
            # buffers never fight the mesh sharding
            host = [
                (np.asarray(b[0]), np.asarray(b[1]))
                if isinstance(b, tuple)
                else np.asarray(b)
                for b in bufs
            ]
            out = _fn(*host)
            # psum leaves the (replicated) sum on the sub-mesh; the
            # fan-in contracts single-device buffers, so land the
            # partition's copy back on its own device (free when the
            # sub-mesh is just that device)
            return jax.device_put(out, _own)

        return run

    def compile_one(i, program):
        if isinstance(program, SlicedProgram):
            if sliced_strategy == "mesh":
                return _mesh_fn(i, program)
            dev = comm.devices[comm.mapping.device(i)]

            def run(bufs, _sp=program, _dev=dev):
                return run_sliced_chunked_placed(
                    _sp,
                    bufs,
                    batch=slice_batch,
                    chunk_steps=chunk_steps,
                    split_complex=split_complex,
                    precision=precision,
                    dtype=dtype,
                    device=_dev,
                    max_slices=max_slices,
                    hoist=hoist,
                    interpret=interpret,
                )

            return run
        # its inputs are resident leaves (_place_partition): not donated
        return jit_program(
            program, split_complex, precision, donate=False,
            interpret=interpret, role="partition_local",
        )

    def run_job(i, fn, bufs):
        # runs on the pool worker thread, so each partition's span lands
        # on its own timeline lane (tid) in the exported trace
        dev = comm.mapping.device(i)
        with obs.span(
            "partitioned.local_partition",
            partition=i,
            device=dev,
        ):
            # transient failures retry THIS partition in place (bounded,
            # shared policy) instead of killing the pool with the other
            # partitions' finished work; anything that survives the
            # retries is re-raised naming the partition and device
            def _attempt():
                _faults.fault_point("partition.local", partition=i, device=dev)
                return fn(bufs)

            try:
                # no local program donates its inputs, so a retry finds
                # them as the failed dispatch left them
                return _retry.default_policy().run(
                    _attempt, label="partition.local"
                )
            except Exception as exc:  # noqa: BLE001 — annotate and re-raise
                raise PartitionExecutionError(i, dev, exc) from exc

    jobs = [
        (i, compile_one(i, program), list(bufs))
        for i, (program, bufs) in enumerate(zip(comm.programs, buffers))
    ]
    cmacs = [_entry_cmacs(program) for program in comm.programs]
    with obs.phase("partitioned.local", partitions=len(jobs)) as local_sp:
        # the partitions' predicted work, always on: how far the slowest
        # chip is from the mean says what the partitioner's balance costs
        local_sp.add(
            cmacs_max=max(cmacs, default=0.0),
            cmacs_mean=sum(cmacs) / max(len(cmacs), 1),
        )
        if len(jobs) > 1:
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(max_workers=len(jobs)) as pool:
                return list(pool.map(lambda job: run_job(*job), jobs))
        return [run_job(i, fn, bufs) for i, fn, bufs in jobs]


def _buffer_nbytes(buf: Any) -> float:
    """Bytes a held fan-in buffer occupies on device (a (real, imag)
    pair in split mode; best-effort 0.0 when the array type hides it)."""
    try:
        if isinstance(buf, tuple):
            return float(sum(_buffer_nbytes(p) for p in buf))
        return float(buf.size) * float(buf.dtype.itemsize)
    except Exception:  # noqa: BLE001 — accounting must never fail a run
        return 0.0


def plan_fanin_pairs(
    metas: Sequence[LeafTensor], toplevel: Sequence[tuple[int, int]]
) -> tuple[list[ContractionProgram], list[LeafTensor], list[float], LeafTensor]:
    """Precompute the whole fan-in schedule's pair programs: for each
    pair ``(x, y)`` of the communication path, its 2-tensor program, the
    meta of the tensor **moved** (y's, the ICI/DCN payload), and the
    pair's flop count. Returns ``(programs, moved_metas, flops,
    final_meta)``. Hoisting this out of the reduce loop keeps the
    per-level hot path free of planning work — a level's dispatches go
    back-to-back with no host-side program construction between them."""
    pair_meta = list(metas)
    programs: list[ContractionProgram] = []
    moved: list[LeafTensor] = []
    flops: list[float] = []
    for x, y in toplevel:
        program, result_meta = _pair_program(pair_meta[x], pair_meta[y])
        programs.append(program)
        moved.append(pair_meta[y])
        flops.append(float(step_flops(program.steps[0])))
        pair_meta[x] = result_meta
    root = _fanin_survivor(len(metas), toplevel) if toplevel else 0
    return programs, moved, flops, pair_meta[root]


def intermediate_reduce(
    comm: Communication,
    toplevel: Sequence[tuple[int, int]],
    results: list[Any],
    split_complex: bool,
    precision,
    levels: Sequence[Sequence[tuple[int, int]]] | None = None,
) -> tuple[Any, LeafTensor]:
    """Overlapped tree fan-in following the communication path
    (``intermediate_reduce_tensor_network``, ``communication.rs:199-249``):
    for ``(x, y)``, move y's tensor onto x's device and contract there.

    The path is grouped into dependency **levels**
    (:func:`~tnc_tpu.contractionpath.communication_schemes.fanin_levels`
    — derived from the communication scheme's own pair order, so a
    latency-aware schedule priced with the calibrated latency map keeps
    its tree shape). All pairs of a level are independent by
    construction and dispatch back-to-back with **no intervening host
    synchronization** — jax dispatch is asynchronous, so a level's
    device-to-device moves and pair contractions all run concurrently;
    partials stay device-resident between levels (nothing returns to
    the host until the survivor is fetched by the caller). One
    ``partitioned.fanin_level`` span per level records the pair count,
    bytes moved over the interconnect, and pair flops — the reduce
    phase's counts.
    """
    import jax

    from tnc_tpu.ops.split_complex import interpret_for

    interpret = interpret_for(comm.devices[0])
    metas = list(comm.results_meta)
    held: list[Any] = list(results)
    if levels is None:
        from tnc_tpu.contractionpath.communication_schemes import fanin_levels

        levels = fanin_levels(toplevel)
    # program bookkeeping in FLATTENED level order (a caller-supplied
    # level schedule may reorder independent pairs relative to the
    # path; the tree — which tensors meet — is unchanged either way)
    flat = [pair for level in levels for pair in level]
    programs, moved_metas, pair_flops, final_meta = plan_fanin_pairs(
        metas, flat
    )
    proc = _process_index()
    with obs.phase("partitioned.fanin") as fanin_sp:
        # counts, not attributes: a caller's collect_phases() reads them
        # with nothing tracing
        fanin_sp.add(pairs=len(flat), levels=len(levels))
        total_bytes = 0.0
        total_flops = 0.0
        pi = 0
        for li, level in enumerate(levels):
            with obs.phase(
                "partitioned.fanin_level", level=li, pairs=len(level)
            ) as level_sp:
                level_bytes = 0.0
                level_flops = 0.0
                for x, y in level:
                    dev = comm.mapping.device(x)
                    target = comm.devices[dev]
                    logger.debug(
                        "fan-in L%d: partition %d (device %d) <- "
                        "partition %d (device %d)",
                        li, x, dev, y, comm.mapping.device(y),
                    )
                    try:
                        # async: device_put and the pair dispatch both
                        # return immediately; the level's pairs overlap
                        # on their devices while the host loops on
                        moved = jax.device_put(held[y], target)
                        fn = jit_program(
                            programs[pi], split_complex, precision,
                            interpret=interpret, role="fanin_pair",
                        )
                        out = fn([held[x], moved])
                    except Exception as exc:  # noqa: BLE001 — name the site
                        raise PartitionExecutionError(
                            x, dev, exc, process=proc, phase="fanin"
                        ) from exc
                    level_bytes += _buffer_nbytes(held[y])
                    level_flops += pair_flops[pi]
                    held[x] = out
                    held[y] = None
                    pi += 1
                level_sp.add(bytes=level_bytes, flops=level_flops)
                total_bytes += level_bytes
                total_flops += level_flops
        fanin_sp.add(bytes=total_bytes, flops=total_flops)
    root = _fanin_survivor(len(held), flat) if flat else 0
    return held[root], final_meta if flat else comm.results_meta[root]


def process_shard_map(
    k: int, toplevel: Sequence[tuple[int, int]], n_procs: int
) -> tuple[int, ...]:
    """Partition index → owning host process for the process-sharded
    executor. The fan-in survivor is pinned to process 0 (the reference's
    rank-0 contract); the rest round-robin across processes so every
    host carries a near-equal share of the local phase.

    >>> process_shard_map(4, [(0, 1), (2, 3), (0, 2)], 2)
    (0, 1, 0, 1)
    """
    root = _fanin_survivor(k, toplevel) if toplevel else 0
    n_procs = max(int(n_procs), 1)
    owner = [0] * k
    for j, part in enumerate(i for i in range(k) if i != root):
        owner[part] = (j + 1) % n_procs
    return tuple(owner)


def _fetch_host(buf: Any):
    """Device buffer → host numpy payload for the KV transport (a
    (real, imag) numpy pair in split mode)."""
    if isinstance(buf, tuple):
        return tuple(np.asarray(p) for p in buf)
    return np.asarray(buf)


def _process_sharded_contraction(
    tn: CompositeTensor,
    contract_path: ContractionPath,
    dtype: str,
    split_complex: bool | None,
    precision,
    hbm_bytes: int | None,
    local_sliced_strategy: str,
    slice_batch: int,
    chunk_steps: int,
    hoist: bool,
) -> LeafTensor:
    """Multi-host partitioned contraction under
    ``jax.distributed.initialize``: partitions shard across processes
    (:func:`process_shard_map`), each host scatters its partitions onto
    its **local** devices and contracts them concurrently, and the
    fan-in walks the level schedule in process-spanning order — a pair
    whose operands live on one host reduces device-to-device there; a
    cross-host pair ships y's tensor over the coordination-KV
    :func:`broadcast_object` transport (the channel PR 7 hardened
    against the silent-zeros gloo collective) to x's owner, which
    contracts on device. Every process walks the same schedule, so the
    collectives stay in lockstep by construction; the final tensor is
    broadcast from the survivor's owner (process 0) to all processes,
    and the result is **bit-identical** to the single-host executor
    (same pair programs, same per-pair arithmetic, byte-exact
    transport).
    """
    import jax

    n_procs = jax.process_count()
    me = jax.process_index()
    local_devices = jax.local_devices()
    if split_complex is None:
        split_complex = local_devices[0].platform != "cpu"
    from tnc_tpu.ops.split_complex import interpret_for

    interpret = interpret_for(local_devices[0])
    if hbm_bytes is None:
        from tnc_tpu.ops.budget import device_hbm_bytes

        hbm_bytes = device_hbm_bytes(local_devices[0])

    children = list(tn.tensors)
    k = len(children)
    for i, child in enumerate(children):
        if not isinstance(child, CompositeTensor):
            raise TypeError(f"top-level child {i} is not a partition composite")
        if i not in contract_path.nested:
            raise ValueError(f"partition {i} has no nested contraction path")
    owner = process_shard_map(k, contract_path.toplevel, n_procs)
    mine = [i for i in range(k) if owner[i] == me]

    # every process derives ALL partition programs host-side (cheap, no
    # communication): pair programs and result metas must agree
    # everywhere for the schedule to stay in lockstep
    programs: list[Any] = []
    metas: list[LeafTensor] = []
    with obs.span(
        "partitioned.scatter", partitions=len(mine), process=me
    ):
        for i, child in enumerate(children):
            entry, program = _partition_program(
                child, contract_path.nested[i], hbm_bytes
            )
            programs.append(entry)
            metas.append(
                LeafTensor(
                    list(program.result_legs), list(program.result_shape)
                )
            )
        # buffers land only on the owner's local devices
        dev_slot = {
            part: idx % len(local_devices) for idx, part in enumerate(mine)
        }
        buffers = {}
        for i in mine:
            try:
                buffers[i] = _place_partition(
                    children[i], dtype, split_complex,
                    local_devices[dev_slot[i]],
                )
            except Exception as exc:  # noqa: BLE001 — name the site
                raise PartitionExecutionError(
                    i, dev_slot[i], exc, process=me, phase="scatter"
                ) from exc

    # local phase: this host's partitions only, overlapped via the
    # shared thread-pool dispatch path
    sub = Communication(
        DeviceTensorMapping(tuple(dev_slot[i] for i in mine)),
        list(local_devices),
        [programs[i] for i in mine],
        [metas[i] for i in mine],
    )
    try:
        results = local_contract_partitions(
            sub,
            [buffers[i] for i in mine],
            split_complex,
            precision,
            sliced_strategy=local_sliced_strategy,
            dtype=dtype,
            slice_batch=slice_batch,
            chunk_steps=chunk_steps,
            hoist=hoist,
        )
    except PartitionExecutionError as exc:
        # remap the sub-communication's local index to the global
        # partition id so multi-host incident logs name the real site
        raise PartitionExecutionError(
            mine[exc.partition], exc.device, exc.original,
            process=me, phase=exc.phase,
        ) from exc.original
    held: dict[int, Any] = dict(zip(mine, results))

    from tnc_tpu.contractionpath.communication_schemes import fanin_levels

    levels = fanin_levels(contract_path.toplevel)
    flat = [pair for level in levels for pair in level]
    pair_programs, moved_metas, pair_flops, final_meta = plan_fanin_pairs(
        metas, flat
    )
    item_bytes = float(np.dtype(dtype).itemsize)
    # one p2p namespace per fan-in: cross-host pairs move point-to-point
    # (sender publishes, x's owner reads; uninvolved hosts skip the
    # transfer entirely) instead of an all-process broadcast per pair.
    # Every process reserves it — counter alignment — even if no pair
    # of the schedule crosses hosts.
    p2p_seq = p2p_sequence()
    pi = 0
    with obs.span(
        "partitioned.fanin",
        pairs=len(flat), levels=len(levels), process=me,
    ) as fanin_sp:
        total_bytes = 0.0
        total_flops = 0.0
        cross = 0
        for li, level in enumerate(levels):
            with obs.span(
                "partitioned.fanin_level",
                level=li, pairs=len(level), process=me,
            ) as level_sp:
                level_bytes = 0.0
                level_flops = 0.0
                for x, y in level:
                    ox, oy = owner[x], owner[y]
                    moved = None
                    # every moved pair counts the payload (same meta
                    # bytes whether it rides ICI on one host or DCN
                    # across hosts) — keeps interconnect_bytes
                    # comparable with the single-host executor's
                    pair_bytes = (
                        float(np.prod(moved_metas[pi].bond_dims))
                        * item_bytes
                    )
                    if ox == oy:
                        if ox == me:
                            target = local_devices[dev_slot[x]]
                            moved = jax.device_put(held.pop(y), target)
                        level_bytes += pair_bytes
                    else:
                        # cross-host pair: y's owner publishes, x's
                        # owner reads — point-to-point, O(payload) on
                        # the wire; hosts owning neither side never
                        # block on (or unpickle) this tensor
                        cross += 1
                        if p2p_seq is not None:
                            if oy == me:
                                send_object(
                                    _fetch_host(held.pop(y)), p2p_seq, pi
                                )
                            elif ox == me:
                                target = local_devices[dev_slot[x]]
                                moved = jax.device_put(
                                    recv_object(p2p_seq, pi), target
                                )
                        else:
                            # no coordination client: all-process
                            # broadcast fallback (lockstep per pair)
                            payload = (
                                _fetch_host(held.pop(y)) if oy == me else None
                            )
                            obj = broadcast_object(payload, root=oy)
                            if ox == me:
                                target = local_devices[dev_slot[x]]
                                moved = jax.device_put(obj, target)
                        level_bytes += pair_bytes
                    if ox == me:
                        try:
                            fn = jit_program(
                                pair_programs[pi], split_complex, precision,
                                interpret=interpret, role="fanin_pair",
                            )
                            held[x] = fn([held.pop(x), moved])
                        except Exception as exc:  # noqa: BLE001
                            raise PartitionExecutionError(
                                x, dev_slot[x], exc,
                                process=me, phase="fanin",
                            ) from exc
                        level_flops += pair_flops[pi]
                    pi += 1
                level_sp.add(bytes=level_bytes, flops=level_flops)
                total_bytes += level_bytes
                total_flops += level_flops
        fanin_sp.add(bytes=total_bytes, flops=total_flops, cross_pairs=cross)

    root_part = _fanin_survivor(k, flat) if flat else 0
    if not flat:
        final_meta = metas[root_part]
    data = None
    if owner[root_part] == me:
        final = held[root_part]
        if split_complex:
            from tnc_tpu.ops.split_complex import combine_array

            data = combine_array(*final)
        else:
            data = np.asarray(final)
        data = data.reshape(tuple(final_meta.bond_dims))
    # every process returns the same tensor (byte-exact KV transport)
    data = broadcast_object(data, root=owner[root_part])
    return LeafTensor(
        list(final_meta.legs), list(final_meta.bond_dims),
        TensorData.matrix(data),
    )


def distributed_partitioned_contraction(
    tn: CompositeTensor,
    contract_path: ContractionPath,
    devices: list | None = None,
    n_devices: int | None = None,
    dtype: str = "complex64",
    split_complex: bool | None = None,
    precision: str | None = "float32",
    hbm_bytes: int | None = None,
    local_sliced_strategy: str = "chunked",
    slice_batch: int = 8,
    chunk_steps: int = 64,
    hoist: bool = False,
    communication_scheme=None,
    cost_model=None,
    process_sharded: bool | None = None,
) -> LeafTensor:
    """Contract a partitioned network with one partition per device.

    ``tn`` must be the output of ``partition_tensor_network`` (top-level
    children = partitions) and ``contract_path`` must carry a nested path
    per partition plus the toplevel communication schedule — the same
    contract as the reference's distributed pipeline (§3.2 of SURVEY.md).
    ``hbm_bytes`` is the per-device budget (default: what the first
    device reports, :func:`~tnc_tpu.ops.budget.device_hbm_bytes`);
    partitions that exceed it are locally sliced (partitioning × slicing
    composition).
    ``local_sliced_strategy``/``slice_batch``/``chunk_steps`` select the
    executor for those locally sliced partitions ('chunked', or 'mesh'
    when devices beyond the partition count are to share their slices:
    see :func:`local_contract_partitions`); ``hoist=True`` additionally runs each sliced
    partition's slice-invariant stem once (:mod:`tnc_tpu.ops.hoist`).

    ``communication_scheme`` (a :class:`~tnc_tpu.contractionpath.
    communication_schemes.CommunicationScheme`): re-derive the fan-in
    schedule here via :func:`replan_fanin` — with per-partition
    latencies always populated (calibrated seconds under ``cost_model``)
    — instead of trusting ``contract_path.toplevel``.

    ``process_sharded``: shard partitions across host processes
    (:func:`_process_sharded_contraction` — local contraction per host,
    cross-host fan-in over the coordination-KV transport, bit-identical
    to the single-host result). Default (``None``): automatic whenever
    the run is multi-process (``jax.distributed.initialize`` with
    ``jax.process_count() > 1``) *unless* an explicit ``devices`` /
    ``n_devices`` placement was given (the sharded executor places on
    each host's local devices itself, so it would silently ignore
    them — an explicit placement keeps the single-controller path, and
    combining one with ``process_sharded=True`` raises); pass ``False``
    to force the single-controller path (requires all devices
    addressable).
    """
    import jax

    if communication_scheme is not None:
        contract_path = replan_fanin(
            tn, contract_path, communication_scheme, cost_model
        )
    explicit_placement = devices is not None or n_devices is not None
    if process_sharded is None:
        process_sharded = jax.process_count() > 1 and not explicit_placement
    if process_sharded:
        if explicit_placement:
            raise ValueError(
                "process_sharded=True places partitions on each host's "
                "local devices itself; devices/n_devices cannot be "
                "combined with it"
            )
        return _process_sharded_contraction(
            tn, contract_path, dtype, split_complex, precision, hbm_bytes,
            local_sliced_strategy, slice_batch, chunk_steps, hoist,
        )
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            if len(devices) < n_devices:
                raise ValueError(
                    f"need {n_devices} devices, have {len(devices)}"
                )
            devices = devices[:n_devices]
    if split_complex is None:
        split_complex = devices[0].platform != "cpu"

    comm, buffers = scatter_partitions(
        tn, contract_path, devices, dtype, split_complex, hbm_bytes=hbm_bytes
    )
    results = local_contract_partitions(
        comm,
        buffers,
        split_complex,
        precision,
        sliced_strategy=local_sliced_strategy,
        dtype=dtype,
        slice_batch=slice_batch,
        chunk_steps=chunk_steps,
        hoist=hoist,
    )
    final, meta = intermediate_reduce(
        comm, contract_path.toplevel, results, split_complex, precision
    )

    # dispatch is asynchronous: here the host waits for the local phase,
    # the chip-to-chip moves and the pair contractions to end
    with obs.phase("partitioned.fetch") as fetch_sp:
        if split_complex:
            from tnc_tpu.ops.split_complex import combine_array

            data = combine_array(*final)
        else:
            data = np.asarray(final)
        fetch_sp.add(bytes=float(data.nbytes))
    # device buffers live in stored (merged) shape; restore leg granularity
    data = data.reshape(tuple(meta.bond_dims))
    return LeafTensor(list(meta.legs), list(meta.bond_dims), TensorData.matrix(data))


def flatten_partitioned_path(
    tn: CompositeTensor, contract_path: ContractionPath
) -> tuple[list[LeafTensor], list[tuple[int, int]]]:
    """Inline a partitioned path into one flat replace-left path over the
    global leaf list (children in index order, as `flat_leaf_tensors`
    orders them) — the form the slicing planner consumes.

    >>> import random
    >>> from tnc_tpu.contractionpath.repartitioning import compute_solution
    >>> from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor
    >>> tn = CompositeTensor([LeafTensor([0, 1], [2, 2]),
    ...     LeafTensor([1, 2], [2, 2]), LeafTensor([2, 3], [2, 2]),
    ...     LeafTensor([3, 0], [2, 2])])
    >>> ptn, ppath, _, _ = compute_solution(tn, [0, 0, 1, 1],
    ...     rng=random.Random(0))
    >>> leaves, pairs = flatten_partitioned_path(ptn, ppath)
    >>> len(leaves), len(pairs)   # 4 leaves, fully contracted
    (4, 3)
    """
    flat_leaves: list[LeafTensor] = []
    start: dict[int, int] = {}
    children = list(tn.tensors)
    for ci, child in enumerate(children):
        if not isinstance(child, CompositeTensor):
            raise TypeError(f"top-level child {ci} is not a partition composite")
        start[ci] = len(flat_leaves)
        flat_leaves.extend(child.tensors)  # type: ignore[arg-type]

    pairs: list[tuple[int, int]] = []
    rep: dict[int, int] = {}
    for ci, child in enumerate(children):
        local = contract_path.nested[ci].toplevel
        base = start[ci]
        for i, j in local:
            pairs.append((base + i, base + j))
        rep[ci] = base + _fanin_survivor(len(child.tensors), local)
    for x, y in contract_path.toplevel:
        pairs.append((rep[x], rep[y]))
    return flat_leaves, pairs


def distributed_partitioned_sliced_contraction(
    tn: CompositeTensor,
    contract_path: ContractionPath,
    devices: list | None = None,
    n_devices: int | None = None,
    dtype: str = "complex64",
    split_complex: bool | None = None,
    precision: str | None = "float32",
    hbm_bytes: int | None = None,
    target_size: float | None = None,
    max_slices: int | None = None,
) -> tuple[LeafTensor, "Slicing"]:
    """Partitioning × **global** slicing (BASELINE config #5; the
    composition the reference lists as future work,
    ``book/src/future_work.md`` item 2).

    Legs are sliced across the *whole* network — including partition cut
    edges, which shrinks the externals that dominate partition memory —
    and for every slice index each device contracts its partition
    concurrently, the fan-in schedule reduces the per-slice result over
    the devices, and results accumulate on the root device.

    ``target_size`` (elements) fixes the slicing directly; otherwise it
    is derived from ``hbm_bytes`` (default: the device's budget).
    ``max_slices`` caps the loop (benchmark subset mode — the sum is then
    partial). Returns (result leaf, slicing).
    """
    run, slicing, final_meta = partitioned_sliced_executor(
        tn,
        contract_path,
        devices=devices,
        n_devices=n_devices,
        dtype=dtype,
        split_complex=split_complex,
        precision=precision,
        hbm_bytes=hbm_bytes,
        target_size=target_size,
    )
    data = run(max_slices)
    return (
        LeafTensor(
            list(final_meta.legs),
            list(final_meta.bond_dims),
            TensorData.matrix(data),
        ),
        slicing,
    )


def global_slicing_target(hbm_bytes: float) -> float:
    """Per-slice element target for the composed pipeline: padded
    split-complex working set ~8 bytes/elem x ~8 live copies."""
    return max(float(hbm_bytes) / 64.0, 4.0)


def plan_global_slicing(
    flat_leaves, flat_pairs, target_size: float, max_slices: int = 1 << 24
):
    """Find the global slicing for a flattened partitioned path at
    ``target_size`` elements, relaxing the target 4x at a time when it
    needs more slices than ``max_slices`` (the per-slice footprint
    then overshoots the budget — best effort; the caller sees the
    slicing and can re-plan). Host-only: benchmark plan ranking calls
    this without touching devices.

    ``max_slices`` defaults to the executable regime (2^24 sequential
    rounds is already far beyond any practical run); PLAN RANKING may
    pass a deep cap (2^40) so budget-infeasible candidates are
    recognized rather than silently relaxed — an executor must never
    inherit that cap, or a degenerate tiny-peak network turns into a
    billion-iteration slice loop (measured round 5: the multichip
    dryrun's 36-element network)."""
    from tnc_tpu.contractionpath.slicing import find_slicing

    while True:
        try:
            return find_slicing(
                flat_leaves, flat_pairs, target_size, max_slices=max_slices
            )
        except ValueError:
            if target_size > 2.0**62:
                raise
            target_size *= 4.0
            logger.warning(
                "global slicing target relaxed to %g elements", target_size
            )


def partitioned_sliced_executor(
    tn: CompositeTensor,
    contract_path: ContractionPath,
    devices: list | None = None,
    n_devices: int | None = None,
    dtype: str = "complex64",
    split_complex: bool | None = None,
    precision: str | None = "float32",
    hbm_bytes: int | None = None,
    target_size: float | None = None,
    plan_max_slices: int = 1 << 24,
):
    """Compile the partitioned × globally-sliced pipeline once and return
    ``(run, slicing, final_meta)`` where ``run(max_slices=None)`` executes
    the slice loop (partial sum when capped) and returns the accumulated
    host array — compiled executables are reused across calls (the
    benchmark warms up with one slice, then times a subset).

    ``plan_max_slices``: forwarded to :func:`plan_global_slicing` — the
    benchmark passes its deep ranking cap (2^40) so the slicing the
    executor compiles is the SAME one the strategy rank scored (probe
    subsets keep deep slice sets affordable); interactive callers keep
    the executable default."""
    import jax
    import jax.numpy as jnp

    from tnc_tpu.ops.budget import device_hbm_bytes
    from tnc_tpu.ops.sliced import (
        build_sliced_program,
        slice_body,
        slice_indices,
    )
    from tnc_tpu.ops.split_complex import interpret_for, plan_kernels

    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            if len(devices) < n_devices:
                raise ValueError(f"need {n_devices} devices, have {len(devices)}")
            devices = devices[:n_devices]
    if split_complex is None:
        split_complex = devices[0].platform != "cpu"
    interpret = interpret_for(devices[0])

    flat_leaves, flat_pairs = flatten_partitioned_path(tn, contract_path)
    if target_size is None:
        if hbm_bytes is None:
            hbm_bytes = device_hbm_bytes(devices[0])
        target_size = global_slicing_target(hbm_bytes)
    slicing = plan_global_slicing(
        flat_leaves, flat_pairs, target_size, max_slices=plan_max_slices
    )
    logger.debug(
        "global slicing: %d legs, %d slices (target %g elems)",
        len(slicing.legs),
        slicing.num_slices,
        target_size,
    )

    children = list(tn.tensors)
    k = len(children)
    mapping = DeviceTensorMapping.for_path(k, contract_path.toplevel)
    sps = [
        build_sliced_program(child, contract_path.nested[i], slicing)
        for i, child in enumerate(children)
    ]
    metas = [
        LeafTensor(list(sp.program.result_legs), list(sp.program.result_shape))
        for sp in sps
    ]
    buffers = [
        _place_partition(
            child, dtype, split_complex, devices[mapping.device(i)]
        )
        for i, child in enumerate(children)
    ]

    def make_local_fn(sp):
        body = slice_body(
            jnp, sp.program.steps, sp.slot_slices,
            split_complex=split_complex, precision=precision,
            policy=plan_kernels(sp.program) if split_complex else None,
            interpret=interpret,
        )
        return jax.jit(
            lambda bufs, indices: body(list(bufs), indices)[
                sp.program.result_slot
            ]
        )

    local_fns = [make_local_fn(sp) for sp in sps]

    # fan-in pair programs are slice-independent (legs already reduced);
    # the level schedule groups independent pairs so each slice's reduce
    # dispatches a level back-to-back (async) with no host sync between
    # same-level pairs
    from tnc_tpu.contractionpath.communication_schemes import fanin_levels

    levels = fanin_levels(contract_path.toplevel)
    # programs indexed in FLATTENED level order (level grouping may
    # reorder independent pairs relative to the path; the tree — which
    # tensors meet — is unchanged, so the programs and survivor are too)
    flat_pairs = [pair for level in levels for pair in level]
    pair_programs, _moved_metas, pair_flops, final_meta = plan_fanin_pairs(
        metas, flat_pairs
    )
    root = _fanin_survivor(k, flat_pairs) if flat_pairs else 0
    if not flat_pairs:
        final_meta = metas[root]

    def run(max_slices: int | None = None):
        num = slicing.num_slices if max_slices is None else min(
            slicing.num_slices, max_slices
        )
        with obs.span(
            "partitioned.sliced_run", slices=num, partitions=k
        ):
            acc = _run_slices(num)

        if split_complex:
            from tnc_tpu.ops.split_complex import combine_array

            data = combine_array(*acc)
        else:
            data = np.asarray(acc)
        return data.reshape(tuple(final_meta.bond_dims))

    def _fanin_one_slice(held: list, record_spans: bool):
        """One slice's tree reduce: level-grouped async dispatch, the
        survivor stays resident on the root device. Spans (recorded for
        the first slice of a run only — one schedule, many identical
        slices) carry per-level pairs/bytes/flops for the roofline and
        the bench ``distributed`` block."""
        pi = 0
        for li, level in enumerate(levels):
            with (
                obs.span(
                    "partitioned.fanin_level", level=li, pairs=len(level)
                )
                if record_spans
                else contextlib.nullcontext()
            ) as level_sp:
                level_bytes = 0.0
                level_flops = 0.0
                for x, y in level:
                    target = devices[mapping.device(x)]
                    moved = jax.device_put(held[y], target)
                    pair_fn = jit_program(
                        pair_programs[pi], split_complex, precision,
                        donate=False, interpret=interpret,
                        role="fanin_pair",
                    )
                    level_bytes += _buffer_nbytes(held[y])
                    level_flops += pair_flops[pi]
                    held[x] = pair_fn([held[x], moved])
                    held[y] = None
                    pi += 1
                if record_spans and obs.enabled():
                    level_sp.add(bytes=level_bytes, flops=level_flops)
        return held

    def _run_slices(num: int):
        acc = None
        for s in range(num):
            # host (uncommitted) indices: each jit transfers them to its
            # own partition's device
            indices = np.asarray(slice_indices(slicing.dims, s), dtype=np.int32)
            held = [
                fn(bufs, indices) for fn, bufs in zip(local_fns, buffers)
            ]  # async: all devices work concurrently
            held = _fanin_one_slice(held, record_spans=(s == 0))
            if acc is None:
                acc = held[root]
            elif split_complex:
                acc = (acc[0] + held[root][0], acc[1] + held[root][1])
            else:
                acc = acc + held[root]
        return acc

    return run, slicing, final_meta


# process-local counter giving every broadcast_object call a unique,
# deterministic KV key. broadcast_object is a collective: all processes
# call it the same number of times in the same order, so their counters
# agree by construction.
_KV_BCAST_SEQ = 0
_KV_BCAST_TIMEOUT_MS = 120_000


def _coordination_client():
    """The jax distributed coordination-service client (the same TCP
    channel ``jax.distributed.initialize`` already established), or
    ``None`` when unavailable (old jaxlib, or no distributed runtime).
    Private-API access is isolated here on purpose."""
    try:
        from jax._src import distributed

        return distributed.global_state.client
    except Exception:  # noqa: BLE001 — any API drift → collective fallback
        return None


def broadcast_object(
    obj,
    root: int = 0,
    wait_forever: bool = False,
    timeout_s: float | None = None,
):
    """Broadcast any picklable object from host process ``root`` to all
    processes — the generic transport under :func:`broadcast_path` and
    the cross-process fan-in (the reference's serialized MPI broadcast,
    ``mpi/communication.rs:14-28``).

    Identity when running single-process; non-root processes pass any
    value (it is ignored) and receive root's object.

    ``wait_forever``: keep re-arming the KV wait past the transport
    timeout instead of raising — the serving fleet's command channel
    (:mod:`tnc_tpu.serve.multihost`), where a worker legitimately
    blocks on the *next* command through arbitrarily long idle periods.
    The per-call sequence key is armed exactly once, so retried waits
    stay in lockstep with the sender.

    ``timeout_s``: bound EVERY wait in this call (the payload get and
    the cleanup barrier) instead of the 120 s transport default. An
    expired wait raises :class:`TimeoutError` — which
    :func:`~tnc_tpu.resilience.retry.classify_exception` maps to
    TRANSIENT — so an elastic fleet's command round degrades to a
    retry/reassign decision instead of hanging on a dead peer. Ignored
    by ``wait_forever`` (which re-arms by design).

    Transport: the distributed **coordination-service KV store** (root
    ``key_value_set``s the pickled payload under a per-call sequence
    key; everyone else blocks on it) — control-plane metadata rides the
    same reliable TCP channel ``jax.distributed.initialize`` set up,
    not the accelerator data plane. The previous transport
    (``multihost_utils.broadcast_one_to_all``, a device psum) was
    observed to silently return ZEROS for the payload phase on
    oversubscribed CPU/gloo test clusters — a corrupted path, not an
    error — which is exactly the failure mode a control channel must
    not have. The collective path is kept as a verified fallback for
    environments without a coordination client.
    """
    import jax

    if jax.process_count() == 1:
        return obj

    import pickle

    global _KV_BCAST_SEQ
    is_root = jax.process_index() == root

    client = _coordination_client()
    if client is not None:
        import base64

        timeout_ms = (
            max(int(float(timeout_s) * 1000.0), 1)
            if timeout_s is not None else _KV_BCAST_TIMEOUT_MS
        )
        seq = _KV_BCAST_SEQ
        _KV_BCAST_SEQ += 1
        key = f"tnc_tpu/bcast/{root}/{seq}"
        if is_root:
            client.key_value_set(
                key, base64.b64encode(pickle.dumps(obj)).decode("ascii")
            )
        while True:
            try:
                blob = client.blocking_key_value_get(key, timeout_ms)
                break
            except Exception as exc:  # noqa: BLE001 — deadline probe
                if "deadline" in str(exc).lower():
                    if wait_forever:
                        continue  # same key: the sender hasn't spoken yet
                    raise TimeoutError(
                        f"broadcast wait for {key} expired after "
                        f"{timeout_ms} ms (sender dead or stalled)"
                    ) from exc
                raise
        out = pickle.loads(base64.b64decode(blob))
        # reclaim the key: a barrier proves every process has read it,
        # then the root deletes — without this, a long-running job's
        # pickled payloads accumulate in the coordination service
        # forever. Best-effort: on any barrier/delete hiccup the key
        # simply stays resident (leak-not-break) — and a dead peer
        # stalls the live fleet here only for timeout_ms, never forever.
        try:
            client.wait_at_barrier(
                f"tnc_tpu/bcast_done/{root}/{seq}", timeout_ms
            )
            if is_root:
                client.key_value_delete(key)
        except Exception:  # noqa: BLE001 — cleanup must never fail a bcast
            logger.debug("bcast key cleanup skipped for %s", key)
        return out

    from jax.experimental import multihost_utils

    payload = pickle.dumps(obj) if is_root else b""
    # length-prefix phase (the reference broadcasts the length first)
    length = int(
        multihost_utils.broadcast_one_to_all(
            np.int64(len(payload)), is_source=is_root
        )
    )
    buf = np.frombuffer(payload.ljust(length, b"\0"), dtype=np.uint8)
    data = multihost_utils.broadcast_one_to_all(buf, is_source=is_root)
    raw = np.asarray(data).tobytes()
    try:
        return pickle.loads(raw)
    except Exception as exc:
        # turn the silent-zeros corruption mode into a diagnosable error
        raise RuntimeError(
            "collective object broadcast returned a corrupt payload "
            f"({len(raw)} bytes, {sum(b != 0 for b in raw[:64])} non-zero "
            "of the first 64) — the CPU/gloo collective backend on this "
            "host is unreliable; jax's coordination-service client was "
            "unavailable for the KV fallback"
        ) from exc


class GatherLost:
    """Root-side placeholder for a gather slot whose sender never
    delivered within the timeout (dead or stalled process). Carries the
    source process index; only ever appears in :func:`gather_objects`
    output when ``missing_ok=True``."""

    def __init__(self, process: int):
        self.process = int(process)

    def __repr__(self) -> str:
        return f"GatherLost(process={self.process})"


def gather_objects(
    obj,
    root: int = 0,
    timeout_s: float | None = None,
    missing_ok: bool = False,
) -> list | None:
    """Gather one picklable object per process at ``root``: returns the
    per-process list (index = process) on the root, ``None`` elsewhere.
    The collective inverse of :func:`broadcast_object` — and unlike a
    gather built from n-1 broadcasts, only the root reads the payloads
    (each sender ``key_value_set``s under its own slot of one shared
    sequence key; total transfer is O(n · payload), one cleanup barrier
    per call). Every process must call this in the same collective
    order; the serving fleet's batch gather rides it
    (:mod:`tnc_tpu.serve.multihost`).

    ``timeout_s`` bounds the root's whole collection (a shared deadline
    across slots, floor 1 s per remaining slot) and the cleanup barrier
    on every process. An expired slot raises :class:`TimeoutError`
    (TRANSIENT under :func:`~tnc_tpu.resilience.retry.
    classify_exception`) — or, with ``missing_ok=True``, lands a
    :class:`GatherLost` marker in that slot so the caller can reassign
    the lost work instead of failing the round (the elastic fleet's
    worker-loss path, :mod:`tnc_tpu.serve.elastic`).

    Identity when running single-process (returns ``[obj]``). Falls
    back to n-1 :func:`broadcast_object` rounds when the coordination
    client is unavailable.
    """
    import jax

    n = jax.process_count()
    if n == 1:
        return [obj]

    import pickle

    global _KV_BCAST_SEQ
    me = jax.process_index()
    client = _coordination_client()
    if client is None:
        # collective fallback: everyone hears everything (n-1 bcasts)
        parts = []
        for src in range(n):
            got = broadcast_object(obj if me == src else None, root=src)
            parts.append(got)
        return parts if me == root else None

    import base64

    timeout_ms = (
        max(int(float(timeout_s) * 1000.0), 1)
        if timeout_s is not None else _KV_BCAST_TIMEOUT_MS
    )
    seq = _KV_BCAST_SEQ
    _KV_BCAST_SEQ += 1
    prefix = f"tnc_tpu/gather/{root}/{seq}"
    if me != root:
        client.key_value_set(
            f"{prefix}/{me}",
            base64.b64encode(pickle.dumps(obj)).decode("ascii"),
        )
    parts = None
    if me == root:
        parts = [None] * n
        parts[root] = obj
        deadline = time.monotonic() + timeout_ms / 1000.0
        for src in range(n):
            if src == root:
                continue
            remaining_ms = max(
                int((deadline - time.monotonic()) * 1000.0), 1000
            )
            try:
                blob = client.blocking_key_value_get(
                    f"{prefix}/{src}", remaining_ms
                )
            except Exception as exc:  # noqa: BLE001 — deadline probe
                if "deadline" not in str(exc).lower():
                    raise
                if not missing_ok:
                    raise TimeoutError(
                        f"gather wait for process {src} expired after "
                        f"{remaining_ms} ms (process dead or stalled)"
                    ) from exc
                parts[src] = GatherLost(src)
                continue
            parts[src] = pickle.loads(base64.b64decode(blob))
    # reclaim: the barrier proves the root has read every slot, then
    # each sender deletes its own key (best-effort, leak-not-break;
    # a dead peer stalls everyone here only for timeout_ms)
    try:
        client.wait_at_barrier(
            f"tnc_tpu/gather_done/{root}/{seq}", timeout_ms
        )
        if me != root:
            client.key_value_delete(f"{prefix}/{me}")
    except Exception:  # noqa: BLE001 — cleanup must never fail a gather
        logger.debug("gather key cleanup skipped for %s", prefix)
    return parts


def p2p_sequence() -> int | None:
    """Reserve one point-to-point key namespace for the calling
    collective. EVERY process must call this at the same point of the
    same collective (it advances the shared sequence counter, keeping
    all later :func:`broadcast_object` keys aligned) even though only
    a sender/receiver pair touches each :func:`send_object` /
    :func:`recv_object` slot under it. Returns ``None`` when no
    coordination client is available — callers fall back to the
    all-process :func:`broadcast_object` transport."""
    global _KV_BCAST_SEQ
    seq = _KV_BCAST_SEQ
    _KV_BCAST_SEQ += 1
    return seq if _coordination_client() is not None else None


def send_object(obj, seq: int, slot: int) -> None:
    """Point-to-point send: publish ``obj`` under slot ``slot`` of the
    :func:`p2p_sequence` namespace ``seq``. Non-blocking; only the one
    consumer (:func:`recv_object`) reads it — O(payload) total traffic
    where a :func:`broadcast_object` costs O(n_processes · payload) and
    a blocking read on every host."""
    import base64
    import pickle

    _coordination_client().key_value_set(
        f"tnc_tpu/p2p/{seq}/{slot}",
        base64.b64encode(pickle.dumps(obj)).decode("ascii"),
    )


def recv_object(seq: int, slot: int):
    """Point-to-point receive half of :func:`send_object`. The receiver
    is the slot's only consumer, so it reclaims the key itself after
    reading — no fleet barrier (best-effort: a delete hiccup leaks the
    key, never breaks the transfer)."""
    import base64
    import pickle

    client = _coordination_client()
    key = f"tnc_tpu/p2p/{seq}/{slot}"
    blob = client.blocking_key_value_get(key, _KV_BCAST_TIMEOUT_MS)
    out = pickle.loads(base64.b64decode(blob))
    try:
        client.key_value_delete(key)
    except Exception:  # noqa: BLE001 — cleanup must never fail a recv
        logger.debug("p2p key cleanup skipped for %s", key)
    return out


def broadcast_path(path_: ContractionPath, root: int = 0) -> ContractionPath:
    """Share the planner's path with every host process
    (``broadcast_path``, ``communication.rs:32-49``).

    Under JAX's single-controller model a single process plans and
    executes, so this is the identity; in a multi-process run
    (``jax.distributed.initialize``) the path found by the ``root``
    process is broadcast to all others as serialized bytes over the
    global mesh, the analogue of the reference's two-phase MPI vec
    broadcast (``communication.rs:14-28``).
    """
    return broadcast_object(path_, root=root)


# Reference-named aliases (``mpi/communication.rs:125,199``): the TPU
# executor's scatter/reduce are the same pipeline stages under the
# device-mesh model.
scatter_tensor_network = scatter_partitions
intermediate_reduce_tensor_network = intermediate_reduce
# the reference's generic serialized broadcast (``broadcast_serializing``,
# ``mpi/communication.rs:14-28``) — any picklable object from root to all
broadcast_serializing = broadcast_object
