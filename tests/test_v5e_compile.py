"""Ask the TPU v5e's compiler, without the chip.

Every program here is lowered on ``jax.ShapeDtypeStruct``s that carry a
*described* ``v5e:2x2`` device's sharding and compiled with
``interpret=False`` — what Mosaic and XLA:TPU refuse here they refuse
on the chip. Nothing runs, so these say nothing about results or times.

The topology is described inside a module-scoped fixture (only the
xdist worker that is handed this file loads the TPU library); nothing
at import time touches it.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    """The described chip, under the configuration a chip run has: x64
    off (the suite's conftest turns it on; the device path is f32), and
    the persistent compile cache off — a compile for a described device
    is written to it but can never be read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    x64 = jax.config.jax_enable_x64
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_x64", x64)
    jax.config.update("jax_enable_compilation_cache", cache)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _pair_specs(shapes, sharding):
    """Split-complex (real, imag) f32 shape pairs on ``sharding``."""
    return [
        (jax.ShapeDtypeStruct(tuple(s), jnp.float32, sharding=sharding),) * 2
        for s in shapes
    ]


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def _residual_inputs(hp, shapes, sharding):
    """The residual program's inputs as the executors hand them over:
    whole leaves (sliced legs and all) and the prelude's cached
    intermediates, (real, imag) pairs on ``sharding``."""
    from tnc_tpu.ops.chunked import _prelude_fn

    full = _pair_specs(shapes, sharding)
    pins = tuple(full[orig] for _, orig in hp.prelude_inputs)
    cached = iter(
        _on(
            sharding,
            jax.eval_shape(
                _prelude_fn(hp, True, "float32", interpret=False), pins
            ),
        )
    )
    return [
        full[ref] if kind == "leaf" else next(cached)
        for kind, ref in hp.residual_sources
    ]


def _total_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return int(
        ma.argument_size_in_bytes
        + ma.output_size_in_bytes
        + ma.temp_size_in_bytes
    )


@pytest.fixture(scope="module")
def chain_bearing():
    """A 24-qubit depth-8 Sycamore-layout amplitude network, greedy
    path: a program ``chain_groups`` finds fusable runs in."""
    from tnc_tpu.builders.sycamore_circuit import sycamore_circuit
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.ops.program import build_program, flat_leaf_tensors

    tn, _ = sycamore_circuit(
        24, 8, np.random.default_rng(42)
    ).into_amplitude_network("0" * 24)
    path = Greedy(OptMethod.GREEDY).find_path(tn).replace_path()
    program = build_program(tn, path)
    shapes = [leaf.data.into_data().shape for leaf in flat_leaf_tensors(tn)]
    return program, shapes


def _sycamore53_hoisted(target: float):
    """Sycamore-53 m=14 sliced to ``target`` elements, planned by the
    cheapest planner that reaches it (the tests are of shapes at the
    target, not of plan quality): ``(sp, hp, leaf shapes)``."""
    from tnc_tpu.builders.sycamore_circuit import sycamore_circuit
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.contractionpath.slicing import slice_and_reconfigure
    from tnc_tpu.ops.hoist import hoist_sliced_program
    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.ops.sliced import build_sliced_program
    from tnc_tpu.tensornetwork.simplify import simplify_network

    raw, _ = sycamore_circuit(
        53, 14, np.random.default_rng(42)
    ).into_amplitude_network("0" * 53)
    tn = simplify_network(raw)
    result = Greedy(OptMethod.GREEDY).find_path(tn)
    pairs, slicing = slice_and_reconfigure(
        list(tn.tensors), result.ssa_path.toplevel, target
    )
    sp = build_sliced_program(tn, ContractionPath.simple(pairs), slicing)
    hp = hoist_sliced_program(sp)
    assert not hp.is_noop
    shapes = [leaf.data.into_data().shape for leaf in flat_leaf_tensors(tn)]
    return sp, hp, shapes


@pytest.fixture(scope="module")
def northstar():
    """Sycamore-53 m=14 at the 2^29 slice target: the hoisted split, the
    leaf shapes and the budget-clamped slice batch the executor would
    run."""
    from tnc_tpu.ops.budget import clamp_slice_batch

    sp, hp, shapes = _sycamore53_hoisted(2.0**29)
    batch = clamp_slice_batch(
        hp.residual.program, 8, hbm_bytes=V5E_HBM_BYTES
    )
    while sp.slicing.num_slices % batch:
        batch -= 1
    return sp, hp, shapes, batch


def test_interpret_follows_the_backend_device(topo):
    """Interpret mode comes from the device a backend targets, not from
    the process: in this CPU process a backend built for the described
    chip compiles its kernels for real."""
    from tnc_tpu.ops.backends import JaxBackend
    from tnc_tpu.ops.split_complex import interpret_for

    on_chip = JaxBackend(device=topo.devices[0])
    assert on_chip.split_complex and not on_chip.interpret
    on_cpu = JaxBackend(device=jax.devices("cpu")[0])
    assert on_cpu.interpret and not on_cpu.split_complex
    assert not interpret_for(topo.devices[0])
    assert interpret_for() == (jax.devices()[0].platform == "cpu")


def test_default_policy_compiles_for_chain_bearing_program(
    one_chip, chain_bearing
):
    """The unforced policy on a program that has chain groups plans no
    chain and compiles (before this test existed it planned three
    kinds of Pallas chain call here and Mosaic refused the program)."""
    from tnc_tpu.ops.backends import jit_program
    from tnc_tpu.ops.split_complex import plan_kernels

    program, shapes = chain_bearing
    assert plan_kernels(program, force="chain").chains
    policy = plan_kernels(program)
    assert policy.chains == ()
    fn = jit_program(
        program, True, "float32", donate=False, policy=policy,
        interpret=False,
    )
    fn.jitted.lower(_pair_specs(shapes, one_chip)).compile()


def test_batched_program_donates_its_stacked_slots_only(
    one_chip, chain_bearing
):
    """The served batch's executable as ``JaxBackend.execute_batched``
    compiles it (donating): ``(stacked, shared)`` arguments, of which
    only the stacked bras are donated, so the shared gate leaves can
    stay resident behind ``place_buffers``."""
    from tnc_tpu.ops.backends import jit_program

    program, shapes = chain_bearing
    # the 24 kets and 24 bras: every vector leaf carries the batch axis
    bras = frozenset(i for i, s in enumerate(shapes) if tuple(s) == (2,))
    assert len(bras) == 48 < len(shapes)
    fn = jit_program(
        program, True, "float32", donate=True, batched=bras,
        interpret=False,
    )
    stacked = _pair_specs([(32, 2)] * len(bras), one_chip)
    shared = _pair_specs(
        [s for i, s in enumerate(shapes) if i not in bras], one_chip
    )
    lowered = fn.jitted.lower(stacked, shared)
    donated = jax.tree.leaves(lowered.args_info[0])
    assert all(a.donated for a in donated[: 2 * len(bras)])
    assert not any(a.donated for a in donated[2 * len(bras):])
    assert "jit_tnc_program_batched" in lowered.as_text()
    lowered.compile()


def test_chain_kernel_is_still_refused(
    one_chip, chain_bearing
):
    """Why the default plans no chain: forced, the chain kernel's
    in-kernel regroup of the carried value is refused by Mosaic when
    the enclosing jit compiles. When this starts to pass, chains can be
    priced into the unforced policy again (ROADMAP C2)."""
    from tnc_tpu.ops.backends import jit_program
    from tnc_tpu.ops.split_complex import plan_kernels

    program, shapes = chain_bearing
    policy = plan_kernels(program, force="chain")
    fn = jit_program(
        program, True, "float32", donate=False, policy=policy,
        interpret=False,
    )
    with pytest.raises(Exception, match="Mosaic failed to compile"):
        fn.jitted.lower(_pair_specs(shapes, one_chip)).compile()


def test_northstar_prelude_compiles(one_chip, northstar):
    from tnc_tpu.ops.chunked import _prelude_fn

    _, hp, shapes, _ = northstar
    full = _pair_specs(shapes, one_chip)
    pins = tuple(full[orig] for _, orig in hp.prelude_inputs)
    compiled = _prelude_fn(hp, True, "float32", interpret=False).lower(
        pins
    ).compile()
    assert _total_bytes(compiled) < V5E_HBM_BYTES


def test_northstar_chunk_compiles_within_hbm(
    one_chip, northstar
):
    """The first chunk of the chunked sliced executor — the one that
    holds the budget model's peak step — at its real shapes and slice
    batch, against 16 GB. Its rows run one after another: the compiled
    program holds no ``dot_general`` with a batch dimension."""
    from tnc_tpu.ops.budget import program_peak_bytes
    from tnc_tpu.ops.chunked import _compiled_plan

    sp, hp, shapes, batch = northstar
    residual = hp.residual
    chunks, chunk_fns, row_modes = _compiled_plan(
        residual, batch, 64, True, "float32", interpret=False
    )
    assert row_modes[0] == "loop"
    assert program_peak_bytes(residual.program).peak_step < len(
        chunks[0].steps
    )
    inputs = _residual_inputs(hp, shapes, one_chip)
    ins = tuple(inputs[slot] for slot in chunks[0].in_slots)
    idx = jax.ShapeDtypeStruct(
        (batch, len(sp.slicing.dims)), jnp.int32, sharding=one_chip
    )
    lowered = chunk_fns[0].lower(ins, idx)
    assert "batching_dims = [0]" not in lowered.as_text()
    compiled = lowered.compile()
    assert _total_bytes(compiled) < V5E_HBM_BYTES


def test_last_chunk_with_a_tensor_valued_sum_compiles(one_chip):
    """Six open legs (a correlated amplitude batch,
    ``queries/amplitude_batch.py``): the last chunk's row sum and Kahan
    fold at a ``(64,)`` stored result, and every chunk before it, for
    the chip's compiler; the accumulator comes back at the stored
    result's shape."""
    from tnc_tpu.builders.sycamore_circuit import sycamore_circuit
    from tnc_tpu.ops.chunked import _compiled_plan
    from tnc_tpu.ops.hoist import hoist_sliced_program
    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.queries.amplitude_batch import bind_amplitude_batch

    prog = bind_amplitude_batch(
        sycamore_circuit(24, 8, np.random.default_rng(42)),
        (3, 8, 9, 14, 19, 23), target_size=2.0**14,
    )
    sp = prog.bound.sliced
    assert sp is not None and sp.slicing.num_slices >= 8
    hp = hoist_sliced_program(sp)
    stored = tuple(hp.residual.program.stored_result_shape)
    assert int(np.prod(stored)) == 64
    shapes = [
        leaf.data.into_data().shape
        for leaf in flat_leaf_tensors(prog.bound.template.network)
    ]
    chunks, chunk_fns, row_modes = _compiled_plan(
        hp.residual, 8, 64, True, "float32", interpret=False
    )
    assert row_modes[-1] == "loop"
    idx = jax.ShapeDtypeStruct(
        (8, len(sp.slicing.dims)), jnp.int32, sharding=one_chip
    )
    state = dict(enumerate(_residual_inputs(hp, shapes, one_chip)))
    for chunk, fn in zip(chunks[:-1], chunk_fns[:-1]):
        ins = tuple(state[slot] for slot in chunk.in_slots)
        fn.lower(ins, idx).compile()
        outs = _on(one_chip, jax.eval_shape(fn, ins, idx))
        state.update(zip(chunk.out_slots, outs))
    part = jax.ShapeDtypeStruct(stored, jnp.float32, sharding=one_chip)
    acc = ((part, part), (part, part))
    ins = tuple(state[slot] for slot in chunks[-1].in_slots)
    compiled = chunk_fns[-1].lower(ins, idx, acc).compile()
    assert _total_bytes(compiled) < V5E_HBM_BYTES
    out = jax.eval_shape(chunk_fns[-1], ins, idx, acc)
    assert {leaf.shape for leaf in jax.tree.leaves(out)} == {stored}


def test_block_rule_moves_fewer_bytes_than_gauss(one_chip):
    """Why the rule: one slice of a Sycamore-53 m=14 residual (Greedy,
    sliced to 2^25: contractions of 2 to 64 on all but a few steps)
    compiles under the default rule, and XLA counts fewer bytes accessed
    for it than for the same program with every step forced to
    ``gauss`` (a compile fact: counts, not speeds)."""
    from tnc_tpu.ops.sliced import program_slice_fn
    from tnc_tpu.ops.split_complex import plan_kernels

    _, hp, shapes = _sycamore53_hoisted(2.0**25)
    program = hp.residual.program
    ruled = plan_kernels(program)
    assert set(ruled.modes) <= {"block", "gauss"}
    assert ruled.modes.count("block") > 0.9 * len(program.steps)
    inputs = _residual_inputs(hp, shapes, one_chip)
    slice_id = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def bytes_accessed(policy):
        one_slice = program_slice_fn(
            jnp, hp.residual, split_complex=True, precision="float32",
            policy=policy, interpret=False,
        )
        cost = jax.jit(one_slice).lower(inputs, slice_id).compile(
        ).cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        return float(cost["bytes accessed"])

    rule = bytes_accessed(ruled)
    gauss = bytes_accessed(plan_kernels(program, force="gauss"))
    assert rule < 0.95 * gauss, (rule, gauss)


def test_fused_complex_dot_compiles(one_chip):
    from jax import lax

    from tnc_tpu.ops.pallas_complex import (
        fused_complex_dot_kl,
        ineligible_reason,
    )

    assert ineligible_reason(512, 1024, 1024) is None
    op = jax.ShapeDtypeStruct((512, 1024), jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda ar, ai, br, bi: fused_complex_dot_kl(
            ar, ai, br, bi, interpret=False,
            precision=lax.Precision.HIGHEST,
        )
    ).lower(op, op, op, op).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_transpose_dot_compiles(one_chip):
    from jax import lax

    from tnc_tpu.ops.pallas_complex import (
        fused_transpose_dot_kl,
        operand_layout,
        transpose_dot_ineligible_reason,
    )

    # first operand stored (M, K) with a macro transpose to (K, M)
    a_lay = operand_layout((1024, 512), (1, 0), (512, 1024), True)
    b_lay = operand_layout((512, 1024), None, (512, 1024), True)
    assert (
        transpose_dot_ineligible_reason(a_lay, b_lay, 512, 1024, 1024)
        is None
    )
    a = jax.ShapeDtypeStruct((1024, 512), jnp.float32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((512, 1024), jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda ar, ai, br, bi: fused_transpose_dot_kl(
            ar, ai, br, bi, a_lay, b_lay, interpret=False,
            precision=lax.Precision.HIGHEST,
        )
    ).lower(a, a, b, b).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_slice_spmd_compiles_for_four_chips(topo):
    """The slice-SPMD function over the four described devices:
    replicated leaves in, each device's share of the slice loop, one
    all-reduce out. (A smaller sliced network than the north-star: the
    53-qubit body compiles too — 142 s, 3.3 GiB per device, asked once
    by hand — but not inside this file's time.)"""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from tnc_tpu.builders.sycamore_circuit import sycamore_circuit
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.contractionpath.slicing import slice_and_reconfigure
    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.ops.sliced import build_sliced_program
    from tnc_tpu.parallel.sliced_parallel import _make_spmd_fn
    from tnc_tpu.tensornetwork.simplify import simplify_network

    raw, _ = sycamore_circuit(
        30, 10, np.random.default_rng(42)
    ).into_amplitude_network("0" * 30)
    tn = simplify_network(raw)
    result = Greedy(OptMethod.GREEDY).find_path(tn)
    pairs, slicing = slice_and_reconfigure(
        list(tn.tensors), result.ssa_path.toplevel, result.size / 64.0
    )
    assert slicing.num_slices % 4 == 0
    sp = build_sliced_program(tn, ContractionPath.simple(pairs), slicing)
    mesh = Mesh(np.asarray(topo.devices), ("slices",))
    assert mesh.shape["slices"] == 4
    fn = _make_spmd_fn(
        sp, mesh, "slices", "complex64", True, "float32", hoist=True
    )
    shapes = [leaf.data.into_data().shape for leaf in flat_leaf_tensors(tn)]
    replicated = NamedSharding(mesh, PartitionSpec())
    compiled = fn.lower(*_pair_specs(shapes, replicated)).compile()
    assert "all-reduce" in compiled.as_text()
    assert _total_bytes(compiled) < V5E_HBM_BYTES
    # the program's op table of the chip's own optimized text, asked for
    # afterwards on the recorded arguments (replicated over the mesh):
    # the same executable, every op a key, the steps' scopes its own
    from tnc_tpu import obs
    from tnc_tpu.obs.op_table import parse_hlo_ops

    ops = set(parse_hlo_ops(compiled.as_text())["ops"])
    variants = obs.device_op_table(["jit_tnc_spmd_slices"])
    # (other SPMD programs of this process may still be remembered)
    (variant,) = [
        v for v in variants["jit_tnc_spmd_slices"] if set(v["ops"]) == ops
    ]
    assert variant["status"] == "ok", variant["why"]
    by_opcode = {}
    for entry in variant["ops"].values():
        by_opcode.setdefault(entry["opcode"], []).append(entry)
    (reduce,) = by_opcode["all-reduce"]
    assert reduce["owners"] == ["tnc.slice.sum"]
    fusions = by_opcode["fusion"]
    owned = [e for e in fusions if len(e["owners"]) == 1]
    assert len(owned) >= 0.95 * len(fusions)
    assert {f["runs"] for f in variant["steps"]} == {"once", "row"}
    assert {n for e in owned for n in e["steps"]} <= {
        f["number"] for f in variant["steps"]
    }


# -- the tiled prep of a block step's streamed operand ---------------------


def _tiled_chain():
    """Two large block steps in a row, the second streaming the first's
    result: steps 39 and 40 of the benchmark's Sycamore-53 residual
    (seed 3000000061), a stem of 2^21 and 2^22 elements a plane stored
    with a minor dim of 256, k = 4 from the rows each time."""
    from tnc_tpu.ops.program import PairStep

    first = PairStep(
        0, 1, (16, 2, 256, 2, 256), (1, 3, 0, 2, 4), (4, 16, 256, 256), True,
        (4, 2, 2, 2), None, (4, 2, 2, 2), True, True, (4, 2, 2048, 2, 256),
    )
    second = PairStep(
        0, 2, (4, 2, 2048, 2, 256), (1, 3, 0, 2, 4), (4, 4, 2048, 256), True,
        (2, 2, 4), None, (2, 2, 4), False, True, (2, 4194304),
    )
    return (first, second), [(16, 2, 256, 2, 256), (4, 2, 2, 2), (2, 2, 4)]


def _walk_fn(steps):
    from tnc_tpu.ops.split_complex import apply_steps_split

    def walk(pairs):
        state = list(pairs)
        apply_steps_split(jnp, steps, state, "float32")
        return state[steps[-1].lhs]

    return jax.jit(walk)


def _entry_graph(text):
    """``name -> (op, operand names, elements)`` of the compiled
    module's entry computation, and the names of its fusions that hold
    a convolution."""
    import math
    import re

    convs, entry, body, name = set(), {}, None, None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$", line)
        if head:
            name, body = head.group(2), "entry" if head.group(1) else "other"
            continue
        if body == "other" and " convolution(" in line:
            convs.add(name)
        inst = re.match(
            r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\w+\[([\d,]*)\]\S*\s+"
            r"([\w\-]+)\((.*?)\)", line,
        )
        if body == "entry" and inst:
            dims = [int(d) for d in inst.group(2).split(",") if d]
            called = re.search(r"calls=%?([\w.\-]+)", line)
            entry[inst.group(1)] = (
                inst.group(3), re.findall(r"%([\w.\-]+)", inst.group(4)),
                math.prod(dims), called.group(1) if called else None,
            )
    dots = [n for n, (_, _, _, called) in entry.items() if called in convs]
    return entry, dots


def test_tiled_chain_has_no_retiling_reshape_before_its_dot(one_chip):
    """The second of two large block steps compiles to two passes over
    its streamed operand: from the first step's convolution through
    bitcasts to ONE ``copy`` (the planned transpose, into the tiled
    image) and through bitcasts to the convolution that reads it — no
    ``reshape`` that is not a bitcast in between. (The first step keeps
    one for the entry parameter's layout.)"""
    from tnc_tpu.ops.split_complex import step_prep_form

    steps, shapes = _tiled_chain()
    assert [step_prep_form(st) for st in steps] == ["tiled", "tiled"]
    text = _walk_fn(steps).lower(
        _pair_specs(shapes, one_chip)
    ).compile().as_text()
    entry, dots = _entry_graph(text)
    assert len(dots) >= 2, dots
    large = 2**18

    def producer(name):
        """Back from an op through bitcasts, along its largest operand."""
        chain = []
        while True:
            operands = [o for o in entry[name][1] if o in entry]
            if not operands:
                return chain
            name = max(operands, key=lambda o: entry[o][2])
            chain.append((entry[name][0], name))
            if entry[name][0] != "bitcast":
                return chain

    last = dots[-1]
    chain = producer(last)
    assert chain[-1][0] == "copy", chain
    assert entry[chain[-1][1]][2] >= large
    before = producer(chain[-1][1])
    assert before[-1][1] in dots[:-1], (chain, before)
    assert not any(op == "reshape" for op, _ in chain + before)


SMALL_DIGEST = "27fc53b464245593"
GAUSS_DIGEST = "4aaf1517c0ade846"


def _lowered_digest(step, shapes, mode=None):
    import hashlib

    from tnc_tpu.ops.split_complex import apply_step_split

    fn = jax.jit(
        lambda a, b: apply_step_split(
            jnp, a, b, step, precision="float32", mode=mode
        )
    )
    specs = [
        (jax.ShapeDtypeStruct(s, jnp.float32),) * 2 for s in shapes
    ]
    return hashlib.sha256(fn.lower(*specs).as_text().encode()).hexdigest()[:16]


def test_size_rule_leaves_small_and_gauss_steps_as_they_were(topo):
    """A block step under 2^18 elements a plane and a gauss step lower
    to the text they lowered to before the tiled prep (digests of the
    StableHLO text taken at commit 0ee9ab0 with this installation): the
    rule reads the step's shape and sends only large block steps on."""
    from tnc_tpu.ops.program import PairStep
    from tnc_tpu.ops.split_complex import default_step_mode, step_prep_form

    small = PairStep(
        0, 1, (256, 4, 128), (1, 0, 2), (4, 256, 128), True,
        (4, 8), None, (4, 8), True, True, (8, 256 * 128),
    )
    gauss = PairStep(
        0, 1, (2**11, 2**8), (1, 0), (2**8, 2**11), True,
        (2**8, 4), None, (2**8, 4), True, True, (4, 2**11),
    )
    assert default_step_mode(small) == "block"
    assert default_step_mode(gauss) == "gauss"
    assert step_prep_form(small) == step_prep_form(gauss) == "matrix"
    assert _lowered_digest(small, [small.a_view, small.b_view]) == SMALL_DIGEST
    assert _lowered_digest(gauss, [gauss.a_view, gauss.b_view]) == GAUSS_DIGEST
