"""Staged operand prep: the tile-padding-safe shuffle path.

Large operands whose naive reshape→transpose would materialize a
high-rank view with tiny trailing dims (XLA tile-pads those 16-128× —
the OOM mode of early benchmark rounds) get a staged op plan from the compiler
(`program._staged_ops`): leading-dim transposes over an intact ≥128
fused tail plus one exact lane permutation. These tests pin (a) the
planner's bit-exactness and minor-dim invariant on randomized
permutations, (b) end-to-end step parity device-vs-oracle for operands
that actually trigger staging, in both lanemix modes, (c) the staged prep
of a streamed operand as ONE Pallas pass (`ops/staged_pass.py`, interpret
mode here): its image is the one-hot matmul's bit for bit, and the rule
that sends a shape to it reads the shape alone.
"""

import math
import os
import random

import numpy as np
import pytest

from tnc_tpu import obs
from _pinned_plan import pinned_residual

from tnc_tpu.ops import split_complex
from tnc_tpu.ops import staged_pass
from tnc_tpu.ops.backends import _prep_operand, apply_step
from tnc_tpu.ops.program import (
    _MIN_MINOR,
    _pair_step,
    _staged_ops,
    operand_prep,
    step_size_class,
    streamed_side,
    tiled_prep_ops,
)
from tnc_tpu.ops.split_complex import (
    apply_step_split,
    apply_steps_split,
    split_array,
    step_prep_form,
)
from tnc_tpu.ops.staged_pass import bit_permutation, pass_plan, staged_image
from tnc_tpu.tensornetwork.tensor import LeafTensor

jnp = pytest.importorskip("jax.numpy")


def _exec_ops_np(x, ops):
    for op in ops:
        if op[0] == "reshape":
            x = x.reshape(op[1])
        elif op[0] == "transpose":
            x = np.transpose(x, op[1])
        else:  # ("lanemix", w, idx)
            x = x.reshape(-1, op[1])[:, list(op[2])]
    return x


def test_staged_ops_randomized_exact():
    rng = random.Random(7)
    planned = 0
    for _ in range(120):
        n = rng.randint(3, 9)
        dims = [rng.choice([2, 2, 4, 4, 8, 16]) for _ in range(n)]
        while math.prod(dims) > 1 << 20:
            dims[rng.randrange(n)] = 2
        perm = list(range(n))
        rng.shuffle(perm)
        ops = _staged_ops(dims, perm)
        if ops is None:
            continue
        planned += 1
        x = np.arange(math.prod(dims), dtype=np.float64).reshape(dims)
        want = np.transpose(x, perm)
        got = _exec_ops_np(x.reshape(-1), ops).reshape(want.shape)
        assert np.array_equal(got, want), (dims, perm)
        # invariant: no materialization with a lane-padded minor dim
        shape = tuple(dims)
        for op in ops:
            if op[0] == "reshape":
                shape = op[1]
            elif op[0] == "transpose":
                shape = tuple(shape[a] for a in op[1])
            else:
                shape = (math.prod(shape) // op[1], op[1])
            if math.prod(shape) >= _MIN_MINOR * 2:
                assert shape[-1] >= _MIN_MINOR, (dims, perm, op, shape)
    assert planned > 30  # the generator must actually exercise the planner


def _interleaved_step():
    """A step whose big operand has contract/free legs alternating in
    storage — the naive prep's worst case (rank-10 view, minor dim 4)."""
    c = [1, 2, 3, 4, 5]
    f = [6, 7, 8, 9, 10]
    legs_a = [c[0], f[0], c[1], f[1], c[2], f[2], c[3], f[3], c[4], f[4]]
    ta = LeafTensor(legs_a, [4] * 10)  # 4^10 = 1M elements: staged fires
    tb = LeafTensor([c[4], c[3], c[2], c[1], c[0], 11], [4] * 6)
    step, out = _pair_step(0, 1, ta, tb)
    assert step.a_ops is not None, "test premise: big operand must stage"
    rng = np.random.default_rng(0)
    a = (rng.standard_normal(4**10) + 1j * rng.standard_normal(4**10)).reshape(
        [4] * 10
    )
    b = (rng.standard_normal(4**6) + 1j * rng.standard_normal(4**6)).reshape(
        [4] * 6
    )
    return step, a, b


@pytest.mark.parametrize("lanemix", ["matmul", "take"])
def test_staged_step_parity_complex(lanemix, monkeypatch):
    monkeypatch.setenv("TNC_TPU_LANEMIX", lanemix)
    step, a, b = _interleaved_step()
    want = apply_step(np, a.astype(np.complex128), b.astype(np.complex128), step)
    got = np.asarray(
        apply_step(
            jnp, jnp.asarray(a, "complex64"), jnp.asarray(b, "complex64"), step
        )
    )
    scale = np.max(np.abs(want))
    assert np.max(np.abs(got - want)) / scale < 1e-5


def test_split_step_numpy_host_path_matches_complex():
    """The numpy host path of apply_step_split (Gauss 3-matmul on split
    parts, swap and no-swap orientations) equals the complex step."""
    step, a, b = _interleaved_step()
    want = np.asarray(
        apply_step(np, a.astype(np.complex128), b.astype(np.complex128), step)
    )
    ar, ai = split_array(a, "float64")
    br, bi = split_array(b, "float64")
    re, im = apply_step_split(np, (ar, ai), (br, bi), step)
    got = re + 1j * im
    scale = np.max(np.abs(want))
    assert np.max(np.abs(got - want)) / scale < 1e-12


def test_staged_step_parity_split_complex():
    step, a, b = _interleaved_step()
    want = np.asarray(
        apply_step(np, a.astype(np.complex128), b.astype(np.complex128), step)
    )
    ar, ai = split_array(a)
    br, bi = split_array(b)
    re, im = apply_step_split(
        jnp,
        (jnp.asarray(ar), jnp.asarray(ai)),
        (jnp.asarray(br), jnp.asarray(bi)),
        step,
        precision="float32",
    )
    got = np.asarray(re) + 1j * np.asarray(im)
    scale = np.max(np.abs(want))
    assert np.max(np.abs(got - want)) / scale < 1e-5


# -- the staged prep of a streamed operand as one Pallas pass --------------


def _clean_env(monkeypatch):
    for name in list(os.environ):
        if name.startswith("TNC_TPU_"):
            monkeypatch.delenv(name)


def _streamed(dims, contract, new):
    """A step that streams a tensor of ``dims`` against a small one that
    shares ``contract`` and brings ``new`` legs of its own."""
    big = LeafTensor(list(range(len(dims))), list(dims))
    small = LeafTensor(
        list(contract) + [100 + i for i in range(new)],
        [dims[c] for c in contract] + [2] * new,
    )
    step, _ = _pair_step(0, 1, small, big)
    return step


#: name -> (leg extents, contracted legs, new legs, lane window): steps
#: whose streamed operand reaches its tiled image through a staged plan;
#: the windows are those the benchmark's slice plans hold (PERF.md,
#: section 6)
_STAGED = {
    "w256": ((2,) * 18, (6, 13, 0, 8, 3), 2, 256),
    "w512": ((2,) * 18, (16, 6, 9, 4, 15), 2, 512),
    "w1024": ((2,) * 18, (7, 17, 10, 16, 15), 2, 1024),
    "w2048": ((2,) * 18, (12, 13, 14, 17), 1, 2048),
    "extent4": ((2,) * 5 + (4,) + (2,) * 11, (16, 5, 10, 4), 3, 512),
}


def _any_size(monkeypatch):
    """Let the rule take operands of any size: these cases are small."""
    monkeypatch.setattr(staged_pass, "MIN_PASS_ELEMS", 0)


def _staged_prep(name, source=None):
    """``(step, ops, plan)`` of a staged case's streamed operand; ``source``
    as `tiled_prep_ops` takes it."""
    dims, contract, new, window = _STAGED[name]
    step = _streamed(dims, contract, new)
    view, perm, dot, cfirst, ops = operand_prep(step, streamed_side(step))
    assert ops is not None and step_prep_form(step) == "tiled"
    tiled = tiled_prep_ops(view, perm, dot, cfirst, True, source)
    assert [op[1] for op in tiled if op[0] == "lanemix"] == [window]
    plan = pass_plan(2 * math.prod(view), tiled)
    assert plan is not None
    return step, tiled, plan


@pytest.mark.parametrize(
    "case",
    sorted(_STAGED) + ["tiled_source", "vmap", "planes"],
)
def test_the_pass_is_the_one_hot_image(case, monkeypatch):
    """The pass writes the image the one-hot matmul (`_lanemix_jax`)
    writes, bit for bit: every lane window of the slice plans, a digit
    of extent 4, an operand a tiled step left (``source=(rows, n)``), a
    batch under `jax.vmap`; and a whole step through the pass equals the
    same step through the matmul in both planes of its result."""
    import jax

    _clean_env(monkeypatch)
    _any_size(monkeypatch)
    rng = np.random.default_rng(sorted(_STAGED).index(case) if case in _STAGED else 9)
    if case == "planes":
        step, _, _ = _staged_prep("w512")
        a = rng.standard_normal(step.a_view) + 1j * rng.standard_normal(step.a_view)
        b = rng.standard_normal(step.b_view) + 1j * rng.standard_normal(step.b_view)
        pairs = [tuple(map(jnp.asarray, split_array(x))) for x in (a, b)]
        got = apply_step_split(jnp, *pairs, step, precision="float32", interpret=True)
        monkeypatch.setattr(split_complex, "_stream_pass", lambda value, ops: None)
        want = apply_step_split(jnp, *pairs, step, precision="float32", interpret=True)
        for g, w in zip(got, want):
            assert np.array_equal(np.asarray(g), np.asarray(w))
        return
    name = case if case in _STAGED else {"tiled_source": "w512", "vmap": "w1024"}[case]
    dims = _STAGED[name][0]
    total = math.prod(dims)
    source = (total // (4 * 128), 4) if case == "tiled_source" else None
    _, ops, plan = _staged_prep(name, source)
    shape = (2,) + dims if source is None else (source[0], 2 * source[1], 128)
    if case == "vmap":
        shape = (3,) + shape
    x = jnp.asarray(rng.standard_normal(shape), dtype="float32")

    def one_hot(v):
        return _prep_operand(jnp, v, None, None, ops[-1][1], ops)

    def by_pass(v):
        return staged_image(v, plan, interpret=True)

    if case == "vmap":
        one_hot, by_pass = jax.vmap(one_hot), jax.vmap(by_pass)
    want = np.asarray(jax.jit(one_hot)(x))
    got = np.asarray(jax.jit(by_pass)(x))
    assert got.shape == want.shape
    assert np.array_equal(got, want)


def test_the_rule_sends_the_staged_steps_of_the_amp_slices_plan_to_the_pass(
    registry, monkeypatch
):
    """Walked as the chunk programs walk it, the pinned plan has 29 large
    steps; 26 of them stream their operand to the tiled image, 20 through
    a staged plan, and the rule sends exactly the 5 of those whose
    operand holds `MIN_PASS_ELEMS` elements or more to the pass."""
    import jax

    _clean_env(monkeypatch)
    chunks = pinned_residual()
    steps = [st for chunk in chunks for st in chunk]
    assert sum(step_size_class(st) == "large" for st in steps) == 29
    sizes = []
    for st in steps:
        view, perm, dot, cfirst, ops = operand_prep(st, streamed_side(st))
        tiled = tiled_prep_ops(view, perm, dot, cfirst, ops is not None)
        if step_prep_form(st) == "tiled" and any(op[0] == "lanemix" for op in tiled):
            sizes.append(2 * math.prod(view))
    assert len(sizes) == 20
    big = sum(n >= staged_pass.MIN_PASS_ELEMS for n in sizes)
    assert big == 5
    for chunk in chunks:
        shapes = {}
        for st in chunk:
            shapes.setdefault(st.lhs, st.a_view)
            shapes.setdefault(st.rhs, st.b_view)

        def walk(state, chunk=chunk):
            state = dict(state)
            apply_steps_split(jnp, chunk, state, "float32", interpret=True)
            return state[chunk[-1].lhs]

        jax.eval_shape(walk, {
            slot: (jax.ShapeDtypeStruct(view, "float32"),) * 2
            for slot, view in shapes.items()
        })
    assert obs.counters_by_prefix("ops.step_prep")["ops.step_prep{form=tiled}"] == 26
    assert obs.counters_by_prefix("ops.lanemix") == {
        "ops.lanemix{impl=pass}": float(big),
        "ops.lanemix{impl=matmul}": float(len(sizes) - big),
    }


@pytest.mark.parametrize(
    "dims, contract, new, why",
    [
        ((2,) * 18, (15,), 2, "image rows"),  # k = 2: an image of 4 rows
        ((2,) * 19, (15, 17, 12, 14, 16, 13), 3, "window"),  # W = 8192
        ((2,) * 18, (16, 6, 9, 4, 15), 2, "size"),  # 2^19 elements
    ],
    ids=["rows", "window", "size"],
)
def test_a_shape_outside_the_rule_keeps_the_one_hot_matmul(
    dims, contract, new, why, registry, monkeypatch
):
    """A staged prep the rule does not take (an image whose row count is
    no multiple of 8; a lane window wider than `MAX_WINDOW`; an operand
    of fewer than `MIN_PASS_ELEMS` elements) runs the one-hot matmul,
    counted ``ops.lanemix{impl=matmul}``, and the step is still the
    oracle's."""
    import jax

    _clean_env(monkeypatch)
    step = _streamed(dims, contract, new)
    view, perm, dot, cfirst, ops = operand_prep(step, streamed_side(step))
    tiled = tiled_prep_ops(view, perm, dot, cfirst, ops is not None)
    window = [op[1] for op in tiled if op[0] == "lanemix"]
    n_elems = 2 * math.prod(view)
    assert window and pass_plan(n_elems, tiled) is None
    if why == "size":
        assert n_elems < staged_pass.MIN_PASS_ELEMS
        _any_size(monkeypatch)  # every other condition holds
        assert pass_plan(n_elems, tiled) is not None
        monkeypatch.undo()
        _clean_env(monkeypatch)
    else:
        _any_size(monkeypatch)  # the size is not why
        assert pass_plan(n_elems, tiled) is None
    if why == "window":
        assert window[0] > staged_pass.MAX_WINDOW
    elif why == "image rows":
        assert tiled[-1][1][-2] % 8
    rng = np.random.default_rng(3)
    a = rng.standard_normal(step.a_view) + 1j * rng.standard_normal(step.a_view)
    b = rng.standard_normal(step.b_view) + 1j * rng.standard_normal(step.b_view)
    pairs = [tuple(map(jnp.asarray, split_array(x))) for x in (a, b)]
    text = jax.jit(
        lambda x, y: apply_step_split(
            jnp, x, y, step, precision="float32", interpret=True
        )
    ).lower(*pairs).as_text()
    assert "tnc_staged_pass" not in text
    assert obs.counters_by_prefix("ops.lanemix") == {"ops.lanemix{impl=matmul}": 1.0}
    want = apply_step(np, a, b, step)
    re, im = apply_step_split(jnp, *pairs, step, precision="float32", interpret=True)
    got = (np.asarray(re) + 1j * np.asarray(im)).reshape(want.shape)
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 1e-5


def test_nothing_catches_a_kernel_error(monkeypatch):
    """A pass that fails fails the step: no path falls back to the matmul."""
    _clean_env(monkeypatch)
    _any_size(monkeypatch)
    step, _, _ = _staged_prep("w256")

    def broken(*args, **kwargs):
        raise RuntimeError("the kernel failed")

    monkeypatch.setattr(staged_pass, "staged_image", broken)
    rng = np.random.default_rng(4)
    pairs = [
        tuple(map(jnp.asarray, split_array(rng.standard_normal(v) + 0j)))
        for v in (step.a_view, step.b_view)
    ]
    with pytest.raises(RuntimeError, match="the kernel failed"):
        apply_step_split(jnp, *pairs, step, precision="float32", interpret=True)


def test_bit_permutation_reads_randomized_staged_plans():
    """On randomized plans of `_staged_ops` with power-of-two digits the
    permutation of index bits is what the ops do to an index array."""
    rng = random.Random(11)
    read = 0
    for _ in range(80):
        n = rng.randint(4, 9)
        dims = [rng.choice([2, 2, 4, 8, 16]) for _ in range(n)]
        while math.prod(dims) > 1 << 18:
            dims[rng.randrange(n)] = 2
        perm = list(range(n))
        rng.shuffle(perm)
        ops = _staged_ops(dims, perm)
        if not ops or not any(op[0] == "lanemix" for op in ops):
            continue
        total = math.prod(dims)
        pi = bit_permutation(total, ops)
        assert pi is not None, (dims, perm)
        read += 1
        got = _exec_ops_np(np.arange(total), ops).reshape(-1)
        j = np.arange(total)
        want = np.zeros_like(j)
        for o, b in enumerate(pi):
            want |= ((j >> o) & 1) << b
        assert np.array_equal(got, want), (dims, perm)
    assert read > 20
