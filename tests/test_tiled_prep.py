"""The tiled prep of a ``block`` step's streamed operand
(`tnc_tpu.ops.program.tiled_prep_ops`,
`tnc_tpu.ops.split_complex._tiled_block_step`): the operand is transposed
straight into the image of its joined ``(2k, M)`` matrix, ``(M / 128, 2k,
128)``, the one real dot contracts axis 1 of it, and a result that the
next tiled step streams stays as the dot wrote it (`TiledValue`).

Held here, on the CPU: hand-made steps (contracted legs in the rows, on
the sublane boundary, inside the lane window; either operand streamed;
either stored order of the result; either orientation of either operand)
and the steps of a Sycamore-layout plan give the numpy oracle's values
as the matrix form does, whatever way the operands come in and the
result goes out; a whole sliced program agrees with complex128 through
the chunked executor and the SPMD entry; the rule reads the step's
shape, and ``ops.step_prep`` / ``kernel_plan_summary(...)["prep"]`` say
how far a program ran in each form.
"""

import math

import numpy as np
import pytest

from tnc_tpu import obs
from tnc_tpu.ops import program as program_mod
from tnc_tpu.ops.backends import apply_step
from tnc_tpu.ops.program import (
    PairStep,
    _pair_step,
    operand_prep,
    step_dims,
    stream_prep_form,
    streamed_side,
    tiled_prep_ops,
)
from tnc_tpu.ops.split_complex import (
    KERNEL_MODES,
    TiledValue,
    apply_step_split,
    apply_steps_split,
    default_step_mode,
    kernel_plan_summary,
    plan_kernel_steps,
    split_array,
    step_prep_form,
)
from tnc_tpu.tensornetwork.tensor import LeafTensor

jnp = pytest.importorskip("jax.numpy")

_LEGS = 19  # the streamed operand: 2^19 elements a plane, lanes = legs 12..18


def _planned(contract, new, stream_first):
    """A step as the program compiler plans it: a 19-leg tensor of
    extent-2 legs against a small one that shares ``contract`` and
    brings ``new`` legs of its own."""
    big = LeafTensor(list(range(_LEGS)), [2] * _LEGS)
    small = LeafTensor(
        list(contract) + [100 + i for i in range(new)],
        [2] * (len(contract) + new),
    )
    step, _ = _pair_step(0, 1, *((big, small) if stream_first else (small, big)))
    return step


def _by_hand(stream_cfirst, expanded_cfirst, swap):
    """A step no plan makes but every executor takes: the streamed
    operand's contracted axis brought from the rows to either end."""
    k, rows, n = 4, 1024, (2, 3)
    if stream_cfirst:
        a_view, a_perm, a_dot = (rows, k, 128), (1, 0, 2), (k, rows, 128)
    else:
        a_view, a_perm, a_dot = (k, rows, 128), (1, 2, 0), (rows, 128, k)
    b_dot = ((k,) + n) if expanded_cfirst else (n + (k,))
    return PairStep(
        lhs=0, rhs=1,
        a_view=a_view, a_perm=a_perm, a_dot=a_dot, a_cfirst=stream_cfirst,
        b_view=b_dot, b_perm=None, b_dot=b_dot, b_cfirst=expanded_cfirst,
        swap=swap, out_store=(rows * 128 * math.prod(n),),
    )


#: name -> (step, form the rule gives it, staged, stored order of the result)
_CASES = {
    "rows_a": (lambda: _planned((2, 5), 2, True), "tiled", False, "nrl"),
    "rows_b": (lambda: _planned((2, 5), 2, False), "tiled", False, "nrl"),
    "rows_korder": (lambda: _planned((5, 2), 3, True), "tiled", False, "nrl"),
    "sublane_a": (lambda: _planned((10, 11), 3, True), "tiled", False, "nrl"),
    "sublane_b": (lambda: _planned((10, 11), 3, False), "tiled", False, "nrl"),
    "lanes_staged_a": (
        lambda: _planned((12, 14, 16, 18), 2, True), "tiled", True, "nrl"),
    "lanes_staged_b": (
        lambda: _planned((13, 16), 2, False), "tiled", True, "nrl"),
    # contracted legs are the lanes' own minor legs: the plan leaves the
    # operand as it lies (contraction last) and the matrix form takes it
    "lanes_last": (lambda: _planned((17, 18), 2, True), "matrix", False, "nrl"),
    # a tie of the trailing free runs: the streamed operand's legs lead
    "stream_first_a": (lambda: _planned((3, 11), 7, True), "tiled", False, "rln"),
    "stream_first_b": (lambda: _planned((11,), 7, False), "tiled", False, "nrl"),
    "wide_result": (lambda: _planned((2, 5), 8, True), "tiled", False, "nrl"),
    "hand_cfirst": (lambda: _by_hand(True, True, True), "tiled", False, "nrl"),
    "hand_clast": (lambda: _by_hand(False, True, True), "tiled", False, "nrl"),
    "hand_expanded_clast": (
        lambda: _by_hand(True, False, True), "tiled", False, "nrl"),
    "hand_noswap": (lambda: _by_hand(True, True, False), "tiled", False, "rln"),
    "hand_clast_noswap": (
        lambda: _by_hand(False, False, False), "tiled", False, "rln"),
}


def _stream(step):
    side = streamed_side(step)
    return (side,) + operand_prep(step, side)


def _operands(step, seed=0):
    rng = np.random.default_rng(seed)

    def draw(view):
        return rng.standard_normal(view) + 1j * rng.standard_normal(view)

    return draw(step.a_view), draw(step.b_view)


def _pairs(a, b, dtype="float32"):
    return (
        tuple(map(jnp.asarray, split_array(a, dtype))),
        tuple(map(jnp.asarray, split_array(b, dtype))),
    )


def _complex(value, stored):
    if isinstance(value, TiledValue):
        value = value.plain()
    re, im = value
    return (np.asarray(re) + 1j * np.asarray(im)).reshape(stored)


def _as_tiled(carried, rows, n, stored):
    """A ``(2,) + stored`` array as a tiled step of ``n`` new elements
    would have left it."""
    return TiledValue(
        jnp.transpose(
            carried.reshape(2, n, rows, 128), (2, 0, 1, 3)
        ).reshape(rows, 2 * n, 128),
        stored,
    )


def _matrix_form(step, pa, pb, monkeypatch, **kwargs):
    """The same step through the matrix form (`_block_step`)."""
    with monkeypatch.context() as patch:
        patch.setattr(program_mod, "stream_prep_form", lambda st: "matrix")
        return apply_step_split(
            jnp, pa, pb, step, precision="float32", mode="block",
            interpret=True, **kwargs,
        )


@pytest.mark.parametrize("case", sorted(_CASES))
def test_tiled_step_is_the_step(case, monkeypatch):
    make, form, staged, stored = _CASES[case]
    step = make()
    side, view, perm, dot, cfirst, ops = _stream(step)
    assert math.prod(view) == 2**_LEGS
    assert default_step_mode(step) == "block"
    assert stream_prep_form(step) == step_prep_form(step) == form
    assert (ops is not None) == staged
    assert ("nrl" if (side == "a") == step.swap else "rln") == stored
    a, b = _operands(step, seed=1)
    want = np.asarray(apply_step(np, a, b, step))  # complex128
    scale = float(np.max(np.abs(want)))
    pa, pb = _pairs(a, b)
    got = _complex(
        apply_step_split(jnp, pa, pb, step, precision="float32", interpret=True),
        step.out_store,
    )
    assert np.max(np.abs(got - want)) / scale < 1e-5
    # as the matrix form does: the same products, summed inside one dot
    matrix = _complex(_matrix_form(step, pa, pb, monkeypatch), step.out_store)
    assert np.max(np.abs(got - matrix)) / scale < 2e-6
    if form == "tiled":
        image = tiled_prep_ops(view, perm, dot, cfirst, staged)
        k = step_dims(step)[1]
        assert image[-1] == ("reshape", (2**_LEGS // (128 * k), 2 * k, 128))
        assert any(op[0] == "lanemix" for op in image) == staged


@pytest.mark.parametrize("carry", [False, True, "tiled"])
@pytest.mark.parametrize("operands", ["pairs", "carried", "tiled"])
@pytest.mark.parametrize(
    "case", ["rows_a", "sublane_b", "lanes_staged_a", "lanes_staged_b",
             "stream_first_a", "wide_result", "hand_clast"]
)
def test_tiled_step_takes_and_hands_back_every_form(case, operands, carry):
    """Operands as pairs, as ``(2,) + stored`` arrays, the streamed one
    as a tiled step left it: bit for bit the values pairs give. The
    result as a pair, carried, or left for the next tiled step: the
    same values (a pair that outweighs the streamed operand is two dots,
    which round apart in the last bit)."""
    step = _CASES[case][0]()
    side, view, perm, dot, cfirst, ops = _stream(step)
    a, b = _operands(step, seed=2)
    pa, pb = _pairs(a, b)
    pair = apply_step_split(
        jnp, pa, pb, step, precision="float32", interpret=True
    )
    want = apply_step_split(
        jnp, pa, pb, step, precision="float32", carry=carry, interpret=True
    )
    want = want.plain() if isinstance(want, TiledValue) else want
    ins = {"a": pa, "b": pb}
    if operands != "pairs":
        ins = {name: jnp.stack(pair) for name, pair in ins.items()}
    if operands == "tiled":
        n = 8  # as a tiled step of n = 8 would have left it
        rows = math.prod(view) // (n * 128)
        assert tiled_prep_ops(
            view, perm, dot, cfirst, ops is not None, (rows, n)
        ) is not None
        ins[side] = _as_tiled(ins[side], rows, n, view)
    got = apply_step_split(
        jnp, ins["a"], ins["b"], step, precision="float32", carry=carry,
        interpret=True,
    )
    stored_nrl = (side == "a") == step.swap
    if carry == "tiled" and stored_nrl:
        assert isinstance(got, TiledValue)
        m, _, n = step_dims(step)
        assert got.source == (max(m, n) // 128, min(m, n))
        got = got.plain()
    if carry:
        assert got.shape == (2,) + tuple(step.out_store)
    else:
        assert isinstance(got, tuple)
    for g, w, p in zip(got, want, pair):
        assert np.array_equal(np.asarray(g), np.asarray(w))
        assert np.allclose(np.asarray(g), np.asarray(p), rtol=0, atol=1e-5)


@pytest.mark.parametrize("lanemix", ["matmul", "take"])
@pytest.mark.parametrize("case", ["lanes_staged_a", "lanes_staged_b"])
def test_tiled_staged_step_under_either_lanemix(case, lanemix, monkeypatch):
    """The window's permutation as the one-hot matmul or as the gather
    (``TNC_TPU_LANEMIX``): the same image."""
    monkeypatch.setenv("TNC_TPU_LANEMIX", lanemix)
    step = _CASES[case][0]()
    a, b = _operands(step, seed=5)
    want = np.asarray(apply_step(np, a, b, step))
    got = _complex(
        apply_step_split(
            jnp, *_pairs(a, b), step, precision="float32", interpret=True
        ),
        step.out_store,
    )
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 1e-5


@pytest.mark.parametrize("batched", ["stream", "small", "both"])
@pytest.mark.parametrize("case", ["rows_a", "lanes_staged_b"])
def test_tiled_step_under_vmap(case, batched):
    """The served batch's shape: ``jax.vmap`` over the step with a
    leading batch axis on either operand; the image is built a rider."""
    import jax

    step = _CASES[case][0]()
    side = streamed_side(step)
    rng = np.random.default_rng(6)
    rows = 3
    ops, axes = {}, []
    for name, view in (("a", step.a_view), ("b", step.b_view)):
        has = batched == "both" or (batched == "stream") == (name == side)
        shape = ((rows,) if has else ()) + tuple(view)
        ops[name] = rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
        axes.append((0, 0) if has else None)
    fn = jax.vmap(
        lambda pa, pb: apply_step_split(
            jnp, pa, pb, step, precision="float32", interpret=True
        ),
        in_axes=tuple(axes),
    )
    re, im = fn(*_pairs(ops["a"], ops["b"]))
    got = np.asarray(re) + 1j * np.asarray(im)
    for row in range(rows):
        a = ops["a"][row] if axes[0] else ops["a"]
        b = ops["b"][row] if axes[1] else ops["b"]
        want = np.asarray(apply_step(np, a, b, step))
        assert np.max(np.abs(got[row] - want)) / np.max(np.abs(want)) < 1e-5


def test_tiled_value_a_matrix_step_reads(monkeypatch):
    """A tiled step's result met by a reader that does not stream it
    tiled (another lowering, the expanded side): one pass brings it to
    stored order."""
    step = _CASES["rows_a"][0]()
    a, b = _operands(step, seed=3)
    pa, pb = _pairs(a, b)
    rows, n = 2**_LEGS // (4 * 128), 4
    tiled = _as_tiled(jnp.stack(pa), rows, n, step.a_view)
    for mode in ("gauss", "naive"):
        want = apply_step_split(
            jnp, pa, pb, step, precision="float32", mode=mode, interpret=True
        )
        got = apply_step_split(
            jnp, tiled, pb, step, precision="float32", mode=mode,
            interpret=True,
        )
        for g, w in zip(got, want):
            assert np.array_equal(np.asarray(g), np.asarray(w))
    want = _matrix_form(step, pa, pb, monkeypatch)
    got = _matrix_form(step, tiled, pb, monkeypatch)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize(
    "view,perm,dot,cfirst,why",
    [
        ((4, 2**15, 100), (0, 1, 2), (4, 2**15, 100), True, "free % 128"),
        ((3, 2**11, 128), (1, 0, 2), (2**11, 3, 128), False, "k not a run"),
    ],
)
def test_what_cannot_be_tiled_is_not(view, perm, dot, cfirst, why):
    assert tiled_prep_ops(view, perm, dot, cfirst) is None, why


# -- the rule and its counter ---------------------------------------------


@pytest.fixture(scope="module")
def sycamore24():
    """A 24-qubit depth-12 Sycamore-layout amplitude network sliced to
    2^20 elements: 128 slices, a residual of 58 steps of which a few
    stream 2^18 elements or more."""
    from tnc_tpu.builders.sycamore_circuit import sycamore_circuit
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.contractionpath.slicing import slice_and_reconfigure
    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.ops.sliced import build_sliced_program
    from tnc_tpu.tensornetwork.simplify import simplify_network

    raw, _ = sycamore_circuit(
        24, 12, np.random.default_rng(42)
    ).into_amplitude_network("0" * 24)
    tn = simplify_network(raw)
    result = Greedy(OptMethod.GREEDY).find_path(tn)
    pairs, slicing = slice_and_reconfigure(
        list(tn.tensors), result.ssa_path.toplevel, 2.0**20
    )
    sp = build_sliced_program(tn, ContractionPath.simple(pairs), slicing)
    assert slicing.num_slices == 128
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]
    return sp, arrays


def _residual(sp):
    from tnc_tpu.ops.hoist import hoist_sliced_program

    return hoist_sliced_program(sp).residual.program


def _clean_env(monkeypatch):
    import os

    for name in list(os.environ):
        if name.startswith("TNC_TPU_"):
            monkeypatch.delenv(name)


def test_the_rule_reads_the_shape_and_nothing_else(sycamore24, monkeypatch):
    """No environment variable, backend option or kernel mode chooses
    the form: with a clean environment the large block steps of a
    Sycamore-layout residual are ``tiled`` and the others ``matrix``; a
    forced lowering other than ``block`` leaves none tiled."""
    _clean_env(monkeypatch)
    assert KERNEL_MODES == (
        "naive", "gauss", "block", "fused", "fused_transpose", "strassen",
        "chain", "auto",
    )
    steps = _residual(sycamore24[0]).steps
    forms = [step_prep_form(st) for st in steps]
    assert 0 < forms.count("tiled") < len(steps)
    for st, form in zip(steps, forms):
        _, view, *_ = _stream(st)
        if form == "tiled":
            assert default_step_mode(st) == "block"
            assert math.prod(view) >= 2**18
            assert (math.prod(view) // step_dims(st)[1]) % 128 == 0
        elif default_step_mode(st) == "block" and math.prod(view) < 2**18:
            assert stream_prep_form(st) == "matrix"
    for forced in ("gauss", "naive"):
        monkeypatch.setenv("TNC_TPU_COMPLEX_MULT", forced)
        assert {step_prep_form(st) for st in steps} == {"matrix"}
    monkeypatch.setenv("TNC_TPU_COMPLEX_MULT", "block")
    assert [step_prep_form(st) for st in steps] == [
        stream_prep_form(st) for st in steps
    ]


def test_plan_summary_and_counter_report_both_forms(
    sycamore24, registry, monkeypatch
):
    _clean_env(monkeypatch)
    program = _residual(sycamore24[0])
    steps = program.steps
    prep = kernel_plan_summary(program)["prep"]
    forms = [step_prep_form(st) for st in steps]
    elems = [math.prod(_stream(st)[1]) for st in steps]
    tiled = sum(e for e, f in zip(elems, forms) if f == "tiled")
    assert prep == {
        form: {
            "steps": forms.count(form),
            "step_share": round(forms.count(form) / len(steps), 4),
            "elems_share": round(
                (tiled if form == "tiled" else sum(elems) - tiled) / sum(elems),
                4,
            ),
        }
        for form in ("matrix", "tiled")
    }
    # 0.53 of the streamed elements before stem fusion joined four of
    # this plan's six large steps into two (PR 36): 0.41 of what is left
    assert prep["tiled"]["elems_share"] > 0.4 > prep["tiled"]["step_share"]
    # one count a traced step, beside ops.step_lowering
    first = forms.index("tiled")
    for st in steps[first - 1:first + 1]:
        a, b = _operands(st)
        apply_step_split(
            jnp, *_pairs(a, b), st, precision="float32", interpret=True
        )
        apply_step_split(
            np, split_array(a, "float64"), split_array(b, "float64"), st
        )  # the host oracle counts nothing
    assert forms[first - 1] == "matrix"
    assert obs.counters_by_prefix("ops.step_prep") == {
        "ops.step_prep{form=matrix}": 1.0,
        "ops.step_prep{form=tiled}": 1.0,
    }
    assert sum(obs.counters_by_prefix("ops.step_lowering").values()) == 2.0


def test_walker_hands_a_tiled_value_to_a_tiled_reader_only(monkeypatch):
    """Two tiled steps in a row carry the image; a result that a matrix
    step, another lowering or the caller reads leaves in stored order."""
    _clean_env(monkeypatch)
    rows = 2**12
    first = PairStep(  # k = 4 from the rows, n = 8
        0, 1, (rows, 4, 128), (1, 0, 2), (4, rows, 128), True,
        (4, 8), None, (4, 8), True, True, (8, rows * 128),
    )
    second = PairStep(  # reads it: k = 2 x 2 (a new leg, a row leg), n = 4
        0, 2, (2, 4, rows // 2, 2, 128), (0, 3, 1, 2, 4),
        (4, 4, rows // 2, 128), True,
        (4, 4), None, (4, 4), True, True, (4, 4 * (rows // 2) * 128),
    )
    last = PairStep(  # k = 2^8: gauss, reads planes
        0, 3, (256, 2**14), None, (256, 2**14), True,
        (256, 2), None, (256, 2), True, True, (2, 2**14),
    )
    steps = (first, second, last)
    assert [step_prep_form(st) for st in steps] == ["tiled", "tiled", "matrix"]
    rng = np.random.default_rng(4)
    arrays = [
        rng.standard_normal(s) + 1j * rng.standard_normal(s)
        for s in (first.a_view, (4, 8), (4, 4), (256, 2))
    ]
    want = list(arrays)
    for st in steps:
        want[st.lhs] = apply_step(np, want[st.lhs], want[st.rhs], st)

    seen = []
    from tnc_tpu.ops import split_complex

    tiled_step = split_complex._tiled_block_step

    def spy(a, b, step, precision, carry, interpret):
        out = tiled_step(a, b, step, precision, carry, interpret)
        seen.append((type(a).__name__, carry, type(out).__name__))
        return out

    monkeypatch.setattr(split_complex, "_tiled_block_step", spy)
    for upto in (1, 2, 3):
        state = [tuple(map(jnp.asarray, split_array(x))) for x in arrays]
        policy = plan_kernel_steps(steps[:upto])
        apply_steps_split(
            jnp, steps[:upto], state, "float32", policy, interpret=True
        )
        assert isinstance(state[0], tuple)
        ref = list(arrays)
        for st in steps[:upto]:
            ref[st.lhs] = apply_step(np, ref[st.lhs], ref[st.rhs], st)
        got = _complex(state[0], steps[upto - 1].out_store)
        assert np.max(np.abs(got - ref[0])) / np.max(np.abs(ref[0])) < 1e-5
    assert seen == [
        ("tuple", False, "tuple"),
        ("tuple", "tiled", "TiledValue"), ("TiledValue", False, "tuple"),
        ("tuple", "tiled", "TiledValue"), ("TiledValue", True, "ArrayImpl"),
    ]


# -- steps of a plan, and a whole program ---------------------------------


def test_every_tiled_step_of_a_sycamore_plan_is_the_step(sycamore24, monkeypatch):
    _clean_env(monkeypatch)
    steps = [
        st for st in _residual(sycamore24[0]).steps
        if step_prep_form(st) == "tiled"
    ]
    assert len(steps) >= 3
    assert any(_stream(st)[5] is not None for st in steps)  # a staged one
    for i, st in enumerate(steps):
        a, b = _operands(st, seed=i)
        want = np.asarray(apply_step(np, a, b, st))
        scale = float(np.max(np.abs(want)))
        pa, pb = _pairs(a, b)
        got = _complex(
            apply_step_split(
                jnp, pa, pb, st, precision="float32", interpret=True
            ),
            st.out_store,
        )
        assert np.max(np.abs(got - want)) / scale < 1e-5
        matrix = _complex(_matrix_form(st, pa, pb, monkeypatch), st.out_store)
        assert np.max(np.abs(got - matrix)) / scale < 2e-6


def _oracle(sp, arrays, lo, hi):
    from tnc_tpu.ops.sliced import sliced_partials_numpy

    parts = sliced_partials_numpy(
        sp, arrays, slice_ids=range(lo, hi), workers=1, hoist=True
    )
    return parts.sum(axis=0)


@pytest.mark.parametrize("chunk_steps", [16, 64])
def test_sycamore24_through_the_chunked_executor(
    sycamore24, registry, chunk_steps, monkeypatch
):
    """Today's tolerance of the sliced executors (float64 planes:
    1e-10) with tiled steps in the per-slice body, values carried across
    them and cut at the chunk boundaries."""
    from tnc_tpu.ops.chunked import execute_sliced_batched_jax

    _clean_env(monkeypatch)
    sp, arrays = sycamore24
    got = execute_sliced_batched_jax(
        sp, arrays, batch=2, chunk_steps=chunk_steps, split_complex=True,
        dtype="complex128", hoist=True, slice_range=(8, 12),
    )
    np.testing.assert_allclose(
        got, _oracle(sp, arrays, 8, 12), rtol=1e-10, atol=1e-14
    )
    counted = obs.counters_by_prefix("ops.step_prep")
    assert counted["ops.step_prep{form=tiled}"] >= 3
    assert counted["ops.step_prep{form=matrix}"] >= 30


def test_sycamore24_in_float32_planes(sycamore24, monkeypatch):
    from tnc_tpu.ops.chunked import execute_sliced_batched_jax

    _clean_env(monkeypatch)
    sp, arrays = sycamore24
    got = execute_sliced_batched_jax(
        sp, arrays, batch=2, chunk_steps=64, split_complex=True,
        dtype="complex64", hoist=True, slice_range=(0, 4), interpret=True,
    )
    want = _oracle(sp, arrays, 0, 4)
    assert abs(complex(got) - complex(want)) / abs(complex(want)) < 1e-4


def test_sycamore24_through_the_spmd_entry_on_a_mesh_of_one(
    sycamore24, monkeypatch
):
    from tnc_tpu.ops.backends import place_buffers
    from tnc_tpu.parallel.sliced_parallel import _make_spmd_fn, make_mesh

    _clean_env(monkeypatch)
    sp, arrays = sycamore24
    fn = _make_spmd_fn(
        sp, make_mesh(1), "slices", "complex128", True, "float32",
        max_slices=4, hoist=True,
    )
    re, im = fn(*place_buffers(arrays, "complex128", True))
    got = np.asarray(re) + 1j * np.asarray(im)
    np.testing.assert_allclose(
        got.reshape(-1), np.asarray(_oracle(sp, arrays, 0, 4)).reshape(-1),
        rtol=1e-10, atol=1e-14,
    )
