"""Staged operand prep: the tile-padding-safe shuffle path.

Large operands whose naive reshape→transpose would materialize a
high-rank view with tiny trailing dims (XLA tile-pads those 16-128× —
the OOM mode of early benchmark rounds) get a staged op plan from the compiler
(`program._staged_ops`): leading-dim transposes over an intact ≥128
fused tail plus one exact lane permutation. These tests pin (a) the
planner's bit-exactness and minor-dim invariant on randomized
permutations, (b) end-to-end step parity device-vs-oracle for operands
that actually trigger staging, in both lanemix modes.
"""

import math
import random

import numpy as np
import pytest

from tnc_tpu.ops.backends import apply_step
from tnc_tpu.ops.program import _MIN_MINOR, _pair_step, _staged_ops
from tnc_tpu.ops.split_complex import apply_step_split, split_array
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
