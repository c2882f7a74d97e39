"""The ``block`` lowering of a split-complex step: ONE real dot with the
contraction twice as long (`tnc_tpu.ops.split_complex._block_step`), the
rule that picks it (`default_step_mode`: ``2k <= 128``), the value
carried as one ``(2,) + stored`` array between the steps of a walker,
and the ``ops.step_lowering`` counter.

Held here, on the CPU: a step in every orientation equals the complex128
oracle and the ``naive`` four dots; a whole sliced program equals the
numpy oracle through the chunked executor, the SPMD entry and the served
batch; callers of a walker see pairs.
"""

import itertools
import math

import numpy as np
import pytest

from tnc_tpu import obs
from tnc_tpu.ops.backends import apply_step
from tnc_tpu.ops.program import PairStep, step_dims
from tnc_tpu.ops.split_complex import (
    BLOCK_MAX_CONTRACT,
    EFFECTIVE_FLOP_FACTOR,
    KERNEL_MODES,
    KernelPolicy,
    apply_step_split,
    apply_steps_split,
    default_step_mode,
    kernel_plan_summary,
    plan_kernel_steps,
    resolved_step_mode,
    split_array,
)

jnp = pytest.importorskip("jax.numpy")


def _step(k, a_free, b_free, a_cfirst=True, b_cfirst=True, swap=False,
          a_perm=None, b_perm=None):
    """A hand-made step: operand ``x`` in dot shape ``(k, *x_free)`` or
    ``(*x_free, k)``, stored so that ``x_perm`` (if any) brings it there."""
    def operand(free, cfirst, perm):
        dot = ((k,) + tuple(free)) if cfirst else (tuple(free) + (k,))
        if perm is None:
            return dot, None, dot
        view = [0] * len(dot)
        for i, src in enumerate(perm):
            view[src] = dot[i]
        return tuple(view), tuple(perm), dot

    a_view, a_perm, a_dot = operand(a_free, a_cfirst, a_perm)
    b_view, b_perm, b_dot = operand(b_free, b_cfirst, b_perm)
    out = math.prod(a_free) * math.prod(b_free)
    return PairStep(
        lhs=0, rhs=1,
        a_view=a_view, a_perm=a_perm, a_dot=a_dot, a_cfirst=a_cfirst,
        b_view=b_view, b_perm=b_perm, b_dot=b_dot, b_cfirst=b_cfirst,
        swap=swap, out_store=(out,),
    )


def _operands(step, seed=0):
    rng = np.random.default_rng(seed)

    def draw(view):
        return rng.standard_normal(view) + 1j * rng.standard_normal(view)

    return draw(step.a_view), draw(step.b_view)


def _run(step, a, b, mode, xp=jnp, dtype="float32", **kwargs):
    pa, pb = split_array(a, dtype), split_array(b, dtype)
    if xp is not np:
        pa, pb = tuple(map(jnp.asarray, pa)), tuple(map(jnp.asarray, pb))
    re, im = apply_step_split(
        xp, pa, pb, step, precision="float32", mode=mode, **kwargs
    )
    return np.asarray(re) + 1j * np.asarray(im)


def _assert_block_is_the_step(step, seed=0):
    a, b = _operands(step, seed)
    want = np.asarray(apply_step(np, a, b, step))  # complex128
    scale = float(np.max(np.abs(want)))
    got = _run(step, a, b, "block")
    assert got.shape == tuple(step.out_store)
    assert np.max(np.abs(got - want)) / scale < 1e-5
    # the arithmetic of naive: the same products, summed inside the dot
    naive = _run(step, a, b, "naive")
    assert np.max(np.abs(got - naive)) / scale < 2e-6
    # the host oracle runs rr - ii, ri + ir for both
    host = _run(step, a, b, "block", xp=np, dtype="float64")
    assert np.array_equal(host, _run(step, a, b, "naive", np, "float64"))
    assert np.max(np.abs(host - want)) / scale < 1e-12


_FREES = {  # (a_free, b_free): either operand the larger, a tie, no free leg
    "a_larger": ((4, 6), (3,)),
    "b_larger": ((2,), (5, 4)),
    "tie": ((3, 2), (6,)),
    "a_vector": ((), (5,)),
    "inner": ((), ()),
}


@pytest.mark.parametrize("k", [1, 2, 64])
@pytest.mark.parametrize("larger", sorted(_FREES))
@pytest.mark.parametrize(
    "a_cfirst,b_cfirst,swap", list(itertools.product([True, False], repeat=3))
)
def test_block_step_in_every_orientation(a_cfirst, b_cfirst, swap, larger, k):
    a_free, b_free = _FREES[larger]
    _assert_block_is_the_step(
        _step(k, a_free, b_free, a_cfirst, b_cfirst, swap), seed=k
    )


@pytest.mark.parametrize("swap", [False, True])
@pytest.mark.parametrize("permuted", ["streamed", "expanded", "both"])
def test_block_step_with_a_permuted_operand(permuted, swap):
    """A macro transpose on the streamed operand (``a``: the larger), on
    the expanded one, on both."""
    step = _step(
        8, (4, 6), (3, 2), swap=swap,
        a_perm=(2, 0, 1) if permuted in ("streamed", "both") else None,
        b_perm=(1, 2, 0) if permuted in ("expanded", "both") else None,
    )
    _assert_block_is_the_step(step, seed=3)


def _staged_step():
    """tests/test_staged_prep.py's interleaved step: the big operand
    carries a staged prep plan (``a_ops``), k = 4^5."""
    from tnc_tpu.ops.program import _pair_step
    from tnc_tpu.tensornetwork.tensor import LeafTensor

    c, f = [1, 2, 3, 4, 5], [6, 7, 8, 9, 10]
    legs_a = [leg for pair in zip(c, f) for leg in pair]
    ta = LeafTensor(legs_a, [4] * 10)
    tb = LeafTensor(c[::-1] + [11], [4] * 6)
    step, _ = _pair_step(0, 1, ta, tb)
    assert step.a_ops is not None, "test premise: big operand must stage"
    return step


@pytest.mark.parametrize("lanemix", ["matmul", "take"])
def test_block_step_with_a_staged_prep_operand(lanemix, monkeypatch):
    monkeypatch.setenv("TNC_TPU_LANEMIX", lanemix)
    step = _staged_step()
    assert default_step_mode(step) == "gauss"  # forced here: k = 1024
    _assert_block_is_the_step(step)


@pytest.mark.parametrize("carried", ["a", "b", "both"])
@pytest.mark.parametrize("case", ["plain", "permuted", "streamed_clast",
                                  "streamed_first", "staged"])
def test_block_step_takes_a_carried_operand(case, carried):
    """An operand as ONE ``(2,) + stored`` array, as the walker hands it
    from a block step to the next: the same result as from the pair."""
    step = {
        "plain": lambda: _step(4, (4, 6), (3,)),
        "permuted": lambda: _step(
            8, (4, 6), (3, 2), swap=True, a_perm=(2, 0, 1), b_perm=(1, 2, 0)
        ),
        "streamed_clast": lambda: _step(4, (4, 6), (3,), a_cfirst=False),
        "streamed_first": lambda: _step(4, (4, 6), (3,), swap=False),
        "staged": _staged_step,
    }[case]()
    a, b = _operands(step, 5)
    pa = tuple(map(jnp.asarray, split_array(a)))
    pb = tuple(map(jnp.asarray, split_array(b)))
    want = apply_step_split(
        jnp, pa, pb, step, precision="float32", mode="block"
    )
    got = apply_step_split(
        jnp,
        jnp.stack(pa) if carried in ("a", "both") else pa,
        jnp.stack(pb) if carried in ("b", "both") else pb,
        step, precision="float32", mode="block", carry=True,
    )
    assert got.shape == (2,) + tuple(step.out_store)
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))


@pytest.mark.parametrize("mode", ["gauss", "naive", "strassen", "fused"])
def test_other_lowerings_take_a_carried_operand_and_hand_back_a_pair(mode):
    step = _step(4, (4, 6), (3,))
    a, b = _operands(step, 7)
    pa = tuple(map(jnp.asarray, split_array(a)))
    pb = tuple(map(jnp.asarray, split_array(b)))
    want = apply_step_split(jnp, pa, pb, step, precision="float32", mode=mode)
    got = apply_step_split(
        jnp, jnp.stack(pa), pb, step, precision="float32", mode=mode,
        interpret=True, carry=True,
    )
    assert isinstance(got, tuple)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("batched", ["a", "b", "both"])
def test_block_step_under_vmap(batched):
    """The served batch's shape: a leading batch axis on either operand
    (a bra is batched, a gate shared), ``jax.vmap`` over the step."""
    import jax

    step = _step(2, (4, 6), (3,), swap=True, a_perm=(1, 2, 0))
    rng = np.random.default_rng(11)
    rows = 5
    ops = {}
    for name, view in (("a", step.a_view), ("b", step.b_view)):
        shape = ((rows,) if name in batched or batched == "both" else ()) + view
        ops[name] = rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
    axes = tuple(
        (0, 0) if name in batched or batched == "both" else None
        for name in "ab"
    )
    fn = jax.vmap(
        lambda pa, pb: apply_step_split(
            jnp, pa, pb, step, precision="float32", mode="block"
        ),
        in_axes=axes,
    )
    pa = tuple(map(jnp.asarray, split_array(ops["a"])))
    pb = tuple(map(jnp.asarray, split_array(ops["b"])))
    re, im = fn(pa, pb)
    got = np.asarray(re) + 1j * np.asarray(im)
    for row in range(rows):
        a = ops["a"][row] if axes[0] else ops["a"]
        b = ops["b"][row] if axes[1] else ops["b"]
        want = np.asarray(apply_step(np, a, b, step))
        assert np.max(np.abs(got[row] - want)) / np.max(np.abs(want)) < 1e-5


# -- the rule -------------------------------------------------------------


@pytest.mark.parametrize(
    "k,want", [(1, "block"), (2, "block"), (64, "block"), (128, "gauss"),
               (2**14, "gauss")]
)
def test_the_rule_reads_the_contraction_alone(k, want, monkeypatch):
    monkeypatch.delenv("TNC_TPU_COMPLEX_MULT", raising=False)
    assert BLOCK_MAX_CONTRACT == 128
    for a_free, b_free in _FREES.values():
        step = _step(k, a_free, b_free)
        assert step_dims(step)[1] == k
        assert default_step_mode(step) == want
        assert resolved_step_mode(step) == want
        assert plan_kernel_steps([step]).modes == (want,)
    monkeypatch.setenv("TNC_TPU_COMPLEX_MULT", "auto")
    assert plan_kernel_steps([step]).modes == (want,)
    assert resolved_step_mode(step) == want


@pytest.mark.parametrize("forced", ["block", "gauss", "naive"])
def test_a_forced_mode_pins_both_sides_of_the_rule(forced, monkeypatch):
    monkeypatch.setenv("TNC_TPU_COMPLEX_MULT", forced)
    steps = [_step(64, (4,), (3,)), _step(128, (4,), (3,))]
    assert plan_kernel_steps(steps).modes == (forced, forced)
    assert [resolved_step_mode(st) for st in steps] == [forced, forced]
    assert plan_kernel_steps(steps, force="gauss").modes == ("gauss",) * 2


def test_block_is_a_kernel_mode_at_full_credit():
    assert "block" in KERNEL_MODES
    assert EFFECTIVE_FLOP_FACTOR["block"] == 1.0


# -- the walker and its counter -------------------------------------------


def _three_step_program():
    """Slots 0..3; steps of k = 4 (block), 4 (block), 256 (gauss), each
    streaming the result of the one before."""
    steps = (
        PairStep(0, 1, (4, 16), None, (4, 16), True, (4, 8), None, (4, 8),
                 True, True, (128,)),
        PairStep(0, 2, (4, 32), None, (4, 32), True, (4, 8), None, (4, 8),
                 True, False, (256,)),
        PairStep(0, 3, (256,), None, (256,), True, (256, 2), None, (256, 2),
                 True, False, (2,)),
    )
    rng = np.random.default_rng(2)
    arrays = [
        rng.standard_normal(s) + 1j * rng.standard_normal(s)
        for s in ((4, 16), (4, 8), (4, 8), (256, 2))
    ]
    return steps, arrays


@pytest.mark.parametrize("state_kind", ["list", "dict"])
def test_walker_carries_between_block_steps_and_hands_back_pairs(
    registry, state_kind, monkeypatch
):
    monkeypatch.delenv("TNC_TPU_COMPLEX_MULT", raising=False)
    steps, arrays = _three_step_program()
    assert [default_step_mode(st) for st in steps] == ["block", "block", "gauss"]
    want = list(arrays)
    for st in steps:
        want[st.lhs] = apply_step(np, want[st.lhs], want[st.rhs], st)

    def fresh():
        pairs = [tuple(map(jnp.asarray, split_array(a))) for a in arrays]
        return pairs if state_kind == "list" else dict(enumerate(pairs))

    for upto in (1, 2, 3):  # a walk that ends on a carried value, too
        state = fresh()
        policy = plan_kernel_steps(steps[:upto])
        apply_steps_split(jnp, steps[:upto], state, "float32", policy)
        assert isinstance(state[0], tuple) and state[upto] is None
        ref = list(arrays)
        for st in steps[:upto]:
            ref[st.lhs] = apply_step(np, ref[st.lhs], ref[st.rhs], st)
        got = np.asarray(state[0][0]) + 1j * np.asarray(state[0][1])
        assert np.max(np.abs(got - ref[0])) / np.max(np.abs(ref[0])) < 1e-5
    assert obs.counters_by_prefix("ops.step_lowering") == {
        "ops.step_lowering{mode=block}": 5.0,  # 1 + 2 + 2
        "ops.step_lowering{mode=gauss}": 1.0,
    }


@pytest.mark.parametrize("upto,dots", [(1, 2), (2, 3), (3, 5)])
def test_walker_carries_to_a_later_step_and_no_further(upto, dots, monkeypatch):
    """A result a later step of the walk reads is ONE dot's (carried
    whole); one that outlives the walk leaves as a pair: a dot a half
    where it outweighs the streamed operand (steps 0 and 1 here), and
    gauss is three."""
    import jax

    monkeypatch.delenv("TNC_TPU_COMPLEX_MULT", raising=False)
    steps, arrays = _three_step_program()

    def walk(pairs):
        state = list(pairs)
        apply_steps_split(jnp, steps[:upto], state, "float32")
        return state[0]

    pairs = [tuple(map(jnp.asarray, split_array(a))) for a in arrays]
    text = jax.jit(walk).lower(pairs).as_text()
    assert text.count("stablehlo.dot_general") == dots
    # block to block no half is cut out of a value; gauss reads planes
    assert ("stablehlo.slice" in text) == (upto == 3)


@pytest.mark.parametrize("k,dots", [(2, 2), (64, 1)])
def test_a_result_that_leaves_as_a_pair(k, dots):
    """Outside a walk a block step hands back a pair: two dots where the
    result outweighs the streamed operand (min(m, n) > k), else the one
    dot and its halves cut."""
    import jax

    step = _step(k, (4, 6), (3,))  # m = 24, n = 3
    a, b = _operands(step)
    pa = tuple(map(jnp.asarray, split_array(a)))
    pb = tuple(map(jnp.asarray, split_array(b)))
    text = jax.jit(
        lambda x, y: apply_step_split(
            jnp, x, y, step, precision="float32", mode="block"
        )
    ).lower(pa, pb).as_text()
    assert text.count("stablehlo.dot_general") == dots


def test_counter_counts_the_arithmetic_that_ran(registry):
    """One count a traced step, under the name after every fallback: a
    ``fused`` step the kernel cannot take is ``naive``, ``strassen``
    below the crossover ``gauss``; the host oracle counts nothing."""
    step = _step(4, (4, 6), (3,))
    a, b = _operands(step)
    for mode in ("block", "gauss", "naive", "fused", "strassen", None):
        _run(step, a, b, mode, interpret=True)
    _run(step, a, b, "block", xp=np, dtype="float64")
    assert obs.counters_by_prefix("ops.step_lowering") == {
        "ops.step_lowering{mode=block}": 2.0,  # asked, and by the rule
        "ops.step_lowering{mode=gauss}": 2.0,
        "ops.step_lowering{mode=naive}": 2.0,
    }


def test_plan_summary_reports_the_share_under_each_mode(monkeypatch):
    from tnc_tpu.ops.program import ContractionProgram, step_flops

    monkeypatch.delenv("TNC_TPU_COMPLEX_MULT", raising=False)
    steps, _ = _three_step_program()
    program = ContractionProgram(
        num_inputs=4, steps=steps, result_slot=0, result_legs=(),
        result_shape=(2,), stored_result_shape=(2,), canonical_legs=(),
    )
    low = kernel_plan_summary(program)["lowering"]
    flops = [step_flops(st) for st in steps]
    assert low == {
        "block": {
            "steps": 2, "step_share": round(2 / 3, 4),
            "flops_share": round(sum(flops[:2]) / sum(flops), 4),
        },
        "gauss": {
            "steps": 1, "step_share": round(1 / 3, 4),
            "flops_share": round(flops[2] / sum(flops), 4),
        },
    }
    forced = kernel_plan_summary(program, KernelPolicy(("naive",) * 3))
    assert forced["lowering"] == {
        "naive": {"steps": 3, "step_share": 1.0, "flops_share": 1.0}
    }


# -- a whole program ------------------------------------------------------


def _oracle(sp, arrays, lo, hi):
    from tnc_tpu.ops.sliced import sliced_partials_numpy

    parts = sliced_partials_numpy(
        sp, arrays, slice_ids=range(lo, hi), workers=1, hoist=True
    )
    return parts.sum(axis=0)


def _residual_modes(sp):
    from tnc_tpu.ops.hoist import hoist_sliced_program

    steps = hoist_sliced_program(sp).residual.program.steps
    return [default_step_mode(st) for st in steps]


@pytest.mark.parametrize("mode", [None, "block", "gauss"])
@pytest.mark.parametrize("chunk_steps", [16, 64])
def test_sycamore20_through_the_chunked_executor(
    sycamore20, registry, chunk_steps, mode, monkeypatch
):
    """Today's tolerance of ``tests/test_chunked_rows.py`` (float64
    planes: 1e-10), under the rule and under either forced side of it."""
    from tnc_tpu.ops.chunked import execute_sliced_batched_jax

    if mode is None:
        monkeypatch.delenv("TNC_TPU_COMPLEX_MULT", raising=False)
    else:
        monkeypatch.setenv("TNC_TPU_COMPLEX_MULT", mode)
    sp, arrays = sycamore20
    got = execute_sliced_batched_jax(
        # a batch size of this test's own, so the plan is traced here
        sp, arrays, batch=2, chunk_steps=chunk_steps, split_complex=True,
        dtype="complex128", hoist=True, slice_range=(8, 24),
    )
    np.testing.assert_allclose(
        got, _oracle(sp, arrays, 8, 24), rtol=1e-10, atol=1e-12
    )
    rule = _residual_modes(sp)
    assert set(rule) == {"block"}  # every residual step: k <= 64
    counted = obs.counters_by_prefix("ops.step_lowering")
    assert counted[f"ops.step_lowering{{mode={mode or 'block'}}}"] >= len(rule)


@pytest.mark.parametrize("mode", [None, "gauss"])
def test_sycamore20_through_the_spmd_entry_on_a_mesh_of_one(
    sycamore20, mode, monkeypatch
):
    from tnc_tpu.ops.backends import place_buffers
    from tnc_tpu.parallel.sliced_parallel import _make_spmd_fn, make_mesh

    if mode is None:
        monkeypatch.delenv("TNC_TPU_COMPLEX_MULT", raising=False)
    else:
        monkeypatch.setenv("TNC_TPU_COMPLEX_MULT", mode)
    sp, arrays = sycamore20
    fn = _make_spmd_fn(
        sp, make_mesh(1), "slices", "complex128", True, "float32",
        max_slices=16, hoist=True,
    )
    re, im = fn(*place_buffers(arrays, "complex128", True))
    got = np.asarray(re) + 1j * np.asarray(im)
    np.testing.assert_allclose(
        got.reshape(-1), np.asarray(_oracle(sp, arrays, 0, 16)).reshape(-1),
        rtol=1e-10, atol=1e-12,
    )


@pytest.mark.parametrize("mode", [None, "gauss"])
def test_sycamore20_as_a_served_batch(sycamore20, mode, monkeypatch):
    """The whole (unsliced) program under ``vmap``: the two-leg leaves of
    lowest index batched, ``JaxBackend.execute_batched`` against the
    numpy backend's."""
    from tnc_tpu.ops.backends import JaxBackend, NumpyBackend

    if mode is None:
        monkeypatch.delenv("TNC_TPU_COMPLEX_MULT", raising=False)
    else:
        monkeypatch.setenv("TNC_TPU_COMPLEX_MULT", mode)
    sp, arrays = sycamore20
    program = sp.program
    # the sliced program's leaves are indexed per slice: run slice 0 whole
    from tnc_tpu.ops.sliced import index_buffer, slice_indices

    indices = slice_indices(sp.slicing.dims, 0)
    leaves = [
        index_buffer(np, np.asarray(a), sp.slot_slices[slot], indices)
        for slot, a in enumerate(arrays)
    ]
    batched = [slot for slot, a in enumerate(leaves) if a.ndim >= 1][:3]
    rng = np.random.default_rng(4)
    rows = 4
    stacked = list(leaves)
    for slot in batched:
        shape = (rows,) + leaves[slot].shape
        stacked[slot] = rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
    want = NumpyBackend(dtype=np.complex128).execute_batched(
        program, stacked, batched
    )
    got = JaxBackend(
        dtype="complex128", split_complex=True, precision="float32"
    ).execute_batched(program, stacked, batched)
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-12)
