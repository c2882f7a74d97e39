"""The chunked executor runs a dispatch's slices one after another
(`tnc_tpu.ops.chunked`): no slice-batch axis reaches a residual step.

Held here, on the CPU: the sum over any slice range equals the numpy
oracle's; one set of programs serves every range; a checkpointed run
resumes bit-identically; the `chunked.rows` counter and the
`sliced.residual` span say how the rows ran; and the lowered
`jit_tnc_residual_*` programs hold exactly the `dot_general`s and
`transpose`s a slice of the SPMD loop's body (`jit_tnc_spmd_slices` on a
mesh of one device): both build a slice from `ops.sliced.slice_body`.
"""

import collections
import re

import numpy as np
import pytest

from tnc_tpu import obs
from tnc_tpu.ops.chunked import (
    _compiled_plan,
    _prelude_fn,
    execute_sliced_batched_jax,
)
from tnc_tpu.ops.hoist import hoist_sliced_program
from tnc_tpu.ops.sliced import (
    build_sliced_program,
    slice_indices,
    sliced_partials_numpy,
)
from tnc_tpu.parallel.sliced_parallel import _make_spmd_fn, make_mesh
from tnc_tpu.resilience import faultinject as fi


def _oracle(sp, arrays, lo, hi):
    parts = sliced_partials_numpy(
        sp, arrays, slice_ids=range(lo, hi), workers=1, hoist=True
    )
    return parts.sum(axis=0)


def _chunked(sp, arrays, split, **kwargs):
    return execute_sliced_batched_jax(
        sp, arrays, batch=8, chunk_steps=16, split_complex=split,
        dtype="complex128", hoist=True, **kwargs,
    )


# (0, 8) is the benchmark's warm-up range and (8, 136) a call of its
# window; 12 slices run as batches of 6, 11 as batches of 1
@pytest.mark.parametrize("split", [False, True])
@pytest.mark.parametrize(
    "lo,hi", [(0, 8), (8, 136), (5, 17), (130, 141)]
)
def test_range_sum_equals_oracle(sycamore20, lo, hi, split):
    sp, arrays = sycamore20
    got = _chunked(sp, arrays, split, slice_range=(lo, hi))
    want = _oracle(sp, arrays, lo, hi)
    np.testing.assert_allclose(got, want, rtol=1e-10, atol=1e-12)


@pytest.mark.parametrize("split", [False, True])
def test_another_range_of_another_length_builds_no_program(
    sycamore20, registry, split
):
    sp, arrays = sycamore20
    residual = hoist_sliced_program(sp).residual
    # a batch size of this test's own, so the plan is not yet cached
    run = dict(
        batch=4, chunk_steps=16, split_complex=split, dtype="complex128",
        hoist=True,
    )
    execute_sliced_batched_jax(sp, arrays, slice_range=(0, 8), **run)
    _, chunk_fns, _ = _compiled_plan(
        residual, 4, 16, split, "float32"
    )
    traced = [fn._cache_size() for fn in chunk_fns]
    assert traced == [1] * len(chunk_fns)
    before = obs.counters_by_prefix("chunk_plan_cache.")
    execute_sliced_batched_jax(sp, arrays, slice_range=(8, 136), **run)
    execute_sliced_batched_jax(sp, arrays, slice_range=(200, 212), **run)
    after = obs.counters_by_prefix("chunk_plan_cache.")
    assert after["chunk_plan_cache.miss"] == before["chunk_plan_cache.miss"]
    assert after["chunk_plan_cache.hit"] == before["chunk_plan_cache.hit"] + 2
    assert [fn._cache_size() for fn in chunk_fns] == traced


@pytest.mark.parametrize("split", [False, True])
@pytest.mark.parametrize("chunk_steps", [16, 64])
def test_resume_mid_range_is_bit_identical(
    sycamore20, tmp_path, monkeypatch, split, chunk_steps
):
    """Killed after three of six batches and restarted from the
    checkpoint, across a chunk boundary that stacks rows (16) and in
    one program a batch (64)."""
    sp, arrays = sycamore20
    monkeypatch.setenv("TNC_TPU_CKPT_EVERY", "1")
    run = dict(
        batch=8, chunk_steps=chunk_steps, split_complex=split,
        dtype="complex128", hoist=True, max_slices=48,
    )
    want = execute_sliced_batched_jax(sp, arrays, **run)
    ckpt = str(tmp_path / "ckpt")
    with fi.faults("chunked.batch(start=24)=fatal"):
        with pytest.raises(fi.InjectedFatal):
            execute_sliced_batched_jax(sp, arrays, ckpt=ckpt, **run)
    assert list((tmp_path / "ckpt").glob("ckpt_*.npz")), "no checkpoint left"
    got = execute_sliced_batched_jax(sp, arrays, ckpt=ckpt, **run)
    assert np.array_equal(got, want)
    np.testing.assert_allclose(
        got, _oracle(sp, arrays, 0, 48), rtol=1e-10, atol=1e-12
    )


@pytest.mark.parametrize(
    "hoist,modes",
    [
        # 36 residual steps in chunks of 16: three dispatches a batch
        (True, {"chunked.rows{mode=loop}": 6.0}),
        # unhoisted, a step a chunk: the stem's steps run once a dispatch
        (False, None),
    ],
)
def test_rows_counter_and_span_attribute(sycamore20, registry, hoist, modes):
    sp, arrays = sycamore20
    chunk_steps = 16 if hoist else 1
    execute_sliced_batched_jax(
        sp, arrays, batch=8, chunk_steps=chunk_steps, split_complex=True,
        dtype="complex128", hoist=hoist, slice_range=(0, 16),
    )
    counted = obs.counters_by_prefix("chunked.rows")
    (span,) = [
        rec for rec in registry.span_records()
        if rec.name == "sliced.residual"
    ]
    assert span.args["rows"] == "loop"
    assert span.args["batch"] == 8
    if modes is not None:
        assert counted == modes
        assert span.args["dispatches"] == 6
        return
    _, _, row_modes = _compiled_plan(sp, 8, chunk_steps, True, "float32")
    stem = len(hoist_sliced_program(sp).prelude_steps)
    assert row_modes.count("once") == stem and row_modes[-1] == "loop"
    assert counted == {
        f"chunked.rows{{mode={mode}}}": 2.0 * row_modes.count(mode)
        for mode in ("loop", "once")
    }


@pytest.mark.parametrize("split", [False, True])
@pytest.mark.parametrize("host", [True, False])
def test_slice_range_on_a_one_slice_program(split, host):
    """An unsliced program is its slice 0: a range that holds it gives
    the value, one that does not gives zeros (stored shape on device)."""
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.contractionpath.slicing import Slicing
    from tnc_tpu.ops.backends import JaxBackend
    from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor
    from tnc_tpu.tensornetwork.tensordata import TensorData

    rng = np.random.default_rng(5)
    mats = [
        rng.standard_normal((4, 4)) + 1j * rng.standard_normal((4, 4))
        for _ in range(3)
    ]
    legs = [[0, 1], [1, 2], [2, 3]]
    tn = CompositeTensor([
        LeafTensor(l, [4, 4], TensorData.matrix(m)) for l, m in zip(legs, mats)
    ])
    sp = build_sliced_program(
        tn, ContractionPath.simple([(0, 1), (0, 2)]), Slicing((), ())
    )
    assert sp.slicing.num_slices == 1
    backend = JaxBackend(dtype="complex128", split_complex=split, donate=False)

    def run(slice_range):
        out = backend.execute_sliced(
            sp, mats, host=host, slice_range=slice_range
        )
        if host:
            assert out.shape == tuple(sp.program.result_shape)
            return np.asarray(out)
        if split:
            out = np.asarray(out[0]) + 1j * np.asarray(out[1])
        assert out.shape == tuple(sp.program.stored_result_shape)
        return np.asarray(out).reshape(sp.program.result_shape)

    want = run(None)
    assert np.abs(want).max() > 0
    for holds in ((0, 1), (0, 5), (-2, 1)):
        np.testing.assert_allclose(run(holds), want, rtol=1e-12)
    for misses in ((1, 3), (0, 0), (-3, 0)):
        assert not run(misses).any(), misses
    with pytest.raises(ValueError, match="exclusive"):
        backend.execute_sliced(sp, mats, max_slices=1, slice_range=(0, 1))


@pytest.mark.parametrize("dims", [(2, 3, 4), (5,), (2, 2, 2, 2), (3, 1, 2)])
def test_slice_id_rule_agrees_with_itself(dims):
    """One rule from a slice id to its leg indices, whatever the id is:
    a Python int (host loops), a numpy vector (the chunked executor's
    index table) or a traced scalar (the on-device loops); last leg
    fastest, as ``np.unravel_index`` counts."""
    import jax
    import jax.numpy as jnp

    num = int(np.prod(dims))
    ids = np.arange(num)
    table = np.stack(slice_indices(dims, ids), axis=1)
    assert table.shape == (num, len(dims))
    np.testing.assert_array_equal(
        table, np.stack(np.unravel_index(ids, dims), axis=1)
    )
    traced = jax.jit(lambda s: jnp.stack(slice_indices(dims, s)))
    for s in range(num):
        ints = slice_indices(dims, s)
        assert all(isinstance(i, int) for i in ints)
        assert ints == table[s].tolist()
        assert np.asarray(traced(jnp.int32(s))).tolist() == ints


# -- the lowered programs -------------------------------------------------

_DOT = re.compile(
    r"stablehlo\.dot_general [^\n]*?(batching_dims[^\n]*?)?contracting_dims"
    r"[^\n]*? : \(([^)]*)\) -> (tensor<[^>]*>)"
)


def _dots(text):
    """Multiset of (operand types, result type) of a module's
    ``dot_general``s; none may carry a batch dimension."""
    found = collections.Counter()
    for batching, operands, result in _DOT.findall(text):
        assert not batching, batching
        found[(operands, result)] += 1
    assert sum(found.values()) == text.count("stablehlo.dot_general ")
    return found


def _transposes(text):
    return text.count("stablehlo.transpose ")


def _rank(tensor_type):
    return tensor_type.count("x")  # tensor<2x2x2xf32> has rank 3


@pytest.mark.parametrize("chunk_steps", [8, 16, 64])
def test_residual_programs_lower_to_the_loop_body(sycamore20, chunk_steps):
    """Every step of a ``jit_tnc_residual_*`` program is the unbatched
    step the SPMD slice loop's body runs (the two slice cells' programs):
    the same ``dot_general``s on the same operand shapes, none with a
    batch dimension, and as many ``transpose``s a slice, but for the
    values a chunk hands to the next."""
    import jax
    import jax.numpy as jnp

    sp, arrays = sycamore20
    hp = hoist_sliced_program(sp)

    def pair(shape, dtype=jnp.float32):
        return (jax.ShapeDtypeStruct(tuple(shape), dtype),) * 2

    full = [pair(a.shape) for a in arrays]
    prelude = _prelude_fn(hp, True, "float32")
    pins = tuple(full[orig] for _, orig in hp.prelude_inputs)
    prelude_text = prelude.lower(pins).as_text()
    loop_text = _make_spmd_fn(
        sp, make_mesh(1), "slices", "complex64", True, "float32", hoist=True
    ).lower(*full).as_text()
    # the loop program traces the prelude before its loop
    body_dots = _dots(loop_text) - _dots(prelude_text)
    body_transposes = _transposes(loop_text) - _transposes(prelude_text)
    # one real dot a step in the block form, three under gauss
    assert sum(body_dots.values()) >= len(hp.residual.program.steps)

    chunks, chunk_fns, row_modes = _compiled_plan(
        hp.residual, 8, chunk_steps, True, "float32"
    )
    assert set(row_modes) == {"loop"}
    cached = iter(jax.eval_shape(prelude, pins))
    state = dict(enumerate(
        full[ref] if kind == "leaf" else next(cached)
        for kind, ref in hp.residual_sources
    ))
    idx = jax.ShapeDtypeStruct((8, len(sp.slicing.dims)), jnp.int32)
    acc = (pair(hp.residual.program.stored_result_shape),) * 2
    dots = collections.Counter()
    transposes = 0
    for ci, (chunk, fn) in enumerate(zip(chunks, chunk_fns)):
        ins = tuple(state[slot] for slot in chunk.in_slots)
        if ci == len(chunks) - 1:
            lowered = fn.lower(ins, idx, acc)
            name = "jit_tnc_residual_last"
        else:
            lowered = fn.lower(ins, idx)
            state.update(zip(chunk.out_slots, jax.eval_shape(fn, ins, idx)))
            name = f"jit_tnc_residual_c{ci:02d}"
        text = lowered.as_text()
        assert f"module @{name} " in text
        assert "stablehlo.while" in text
        dots += _dots(text)
        transposes += _transposes(text)
    # a value a block step carries to the next is one array: permuted
    # once, never cut. Handed to the next chunk it leaves as a pair (a
    # dot a half where it outweighs its operands) and each plane is
    # permuted on its own
    handed = sum(len(chunk.out_slots) for chunk in chunks[:-1])
    assert sum((body_dots - dots).values()) <= handed
    assert sum((dots - body_dots).values()) <= 2 * handed
    assert 0 <= transposes - body_transposes <= handed
    if len(chunks) == 1:
        assert dots == body_dots and transposes == body_transposes
    top = max(_rank(t) for ops, _ in body_dots for t in ops.split(", "))
    assert all(
        _rank(t) <= top for ops, _ in dots for t in ops.split(", ")
    )
