"""Differentiable contraction — a capability the reference cannot offer.

Everything the executor runs is a chain of jittable dots, so JAX
differentiates a whole contraction for free. The natural applications
are variational quantum circuits: the gradient of an expectation value
⟨ψ(θ)|O|ψ(θ)⟩ (or of a single amplitude) with respect to selected leaf
tensors — e.g. parameterized gate matrices — comes from one
reverse-mode sweep over the same compiled program instead of
parameter-shift re-contractions.

Complex leaves follow JAX's reverse-mode convention for real-valued
``f``: the returned cotangent ``g`` of leaf ``T`` satisfies
``df = Re(sum(g * dT))`` for a perturbation ``dT`` (validated entrywise
against finite differences in ``tests/test_autodiff.py``). ``scalar_fn``
defaults to the real part of the fully-contracted scalar.

The reference's Rust stack has no autodiff; this closes the variational
workflow gap TPU-natively (listed as item 4 of docs/future_work.md).
"""

from __future__ import annotations

from typing import Callable, Sequence

import numpy as np

from tnc_tpu.contractionpath.contraction_path import ContractionPath
from tnc_tpu.ops.backends import _run_steps
from tnc_tpu.ops.program import build_program, flat_leaf_tensors
from tnc_tpu.tensornetwork.tensor import CompositeTensor


def _validate_wrt(wrt, n_slots: int) -> list[int]:
    """Flat-slot list for differentiation: in range (no negative
    indexing — slots are flat leaf indices) and duplicate-free (a
    duplicate would shadow the previous tracer and silently yield a
    zero gradient for every occurrence but the last)."""
    wrt = list(wrt)
    if len(set(wrt)) != len(wrt):
        raise ValueError("duplicate slots in wrt")
    for s in wrt:
        if not 0 <= s < n_slots:
            raise ValueError(f"wrt slot {s} out of range 0..{n_slots - 1}")
    return wrt


def contraction_value_and_grad(
    tn: CompositeTensor,
    contract_path: ContractionPath,
    wrt: Sequence[int] | None = None,
    scalar_fn: Callable | None = None,
    dtype: str = "complex64",
):
    """Value and gradient of a contraction w.r.t. selected leaf tensors.

    ``wrt``: flat leaf-slot indices (see `flat_leaf_tensors` order);
    default: all leaves. ``scalar_fn``: maps the (complex) result array
    to a real scalar; default takes the real part of the first element
    (an amplitude/expectation network contracts to a scalar).

    Returns ``(value, grads)`` where ``value`` is the full complex
    result (host array, canonical shape) and ``grads[i]`` is the
    cotangent for ``wrt[i]``, shaped like that leaf.

    The gradient runs through the same whole-path program the forward
    pass uses — no parameter-shift re-contractions. Donation is off (the
    reverse sweep needs the primals).

    >>> from tnc_tpu.builders.circuit_builder import Circuit
    >>> from tnc_tpu.tensornetwork.tensordata import TensorData
    >>> from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    >>> c = Circuit(); reg = c.allocate_register(3)
    >>> c.append_gate(TensorData.gate("h"), [reg.qubit(0)])
    >>> for i in range(2):
    ...     c.append_gate(TensorData.gate("cx"), [reg.qubit(i), reg.qubit(i + 1)])
    >>> tn, _ = c.into_amplitude_network("111")
    >>> path = Greedy(OptMethod.GREEDY).find_path(tn).replace_path()
    >>> value, grads = contraction_value_and_grad(tn, path, wrt=[0])
    >>> abs(complex(value.reshape(-1)[0]) - 2 ** -0.5) < 1e-6
    True
    >>> grads[0].shape   # cotangent shaped like leaf 0
    (2,)
    """
    import jax
    import jax.numpy as jnp

    program = build_program(tn, contract_path)
    leaves = flat_leaf_tensors(tn)
    arrays = [
        jnp.asarray(leaf.data.into_data(), dtype=dtype) for leaf in leaves
    ]
    if wrt is None:
        wrt = list(range(len(arrays)))
    wrt = _validate_wrt(wrt, len(arrays))

    if scalar_fn is None:

        def scalar_fn(result):
            return jnp.real(result.reshape(-1)[0])

    perm = program.canonical_perm()
    dim_of = dict(zip(program.result_legs, program.result_shape))
    canonical_shape = tuple(dim_of[leg] for leg in program.canonical_legs)

    def forward(diff_arrays):
        buffers = list(arrays)
        for slot, arr in zip(wrt, diff_arrays):
            buffers[slot] = arr
        out = _run_steps(jnp, program, buffers).reshape(program.result_shape)
        if perm is not None:
            out = jnp.transpose(out, perm)
        return scalar_fn(out), out

    diff_in = tuple(arrays[slot] for slot in wrt)
    (value_scalar, result), grads = jax.value_and_grad(
        forward, has_aux=True
    )(diff_in)
    del value_scalar
    return (
        np.asarray(result).reshape(canonical_shape),
        [np.asarray(g) for g in grads],
    )


def sliced_contraction_value_and_grad(
    tn: CompositeTensor,
    contract_path: ContractionPath,
    slicing,
    wrt: Sequence[int] | None = None,
    scalar_fn: Callable | None = None,
    dtype: str = "complex64",
):
    """Like :func:`contraction_value_and_grad` for a *sliced* plan: the
    value is the sum over all slice programs, and one reverse-mode sweep
    through the on-device slice loop yields the gradients — the vjp of
    the slice sum is the sum of per-slice vjps, so memory stays at the
    sliced peak (the whole point of slicing) instead of the unsliced
    program's. Closes the "gradients through sliced programs" item of
    docs/future_work.md (#4).

    The slice loop is a ``lax.fori_loop`` with static bounds, which JAX
    converts to a scan for reverse-mode; the body is ``jax.checkpoint``-
    ed so the backward pass stores only the loop carry and recomputes
    per-slice intermediates (without remat, scan-grad stacks every
    slice's residuals — exactly the memory slicing exists to avoid).
    Slice contributions accumulate with the same Kahan compensation as
    the forward executors. Complex dtype path (like the unsliced
    version): run on CPU/``jax64`` for gradient workflows.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from tnc_tpu.ops.sliced import (
        build_sliced_program,
        kahan_add,
        program_slice_fn,
    )

    sp = build_sliced_program(tn, contract_path, slicing)
    leaves = flat_leaf_tensors(tn)
    arrays = [
        jnp.asarray(leaf.data.into_data(), dtype=dtype) for leaf in leaves
    ]
    if wrt is None:
        wrt = list(range(len(arrays)))
    wrt = _validate_wrt(wrt, len(arrays))

    if scalar_fn is None:

        def scalar_fn(result):
            return jnp.real(result.reshape(-1)[0])

    program = sp.program
    perm = program.canonical_perm()
    dim_of = dict(zip(program.result_legs, program.result_shape))
    canonical_shape = tuple(dim_of[leg] for leg in program.canonical_legs)
    num = sp.slicing.num_slices
    one_slice = program_slice_fn(jnp, sp)

    def forward(diff_arrays):
        buffers = list(arrays)
        for slot, arr in zip(wrt, diff_arrays):
            buffers[slot] = arr

        @jax.checkpoint
        def contribution(s):
            return one_slice(buffers, s)

        def body(s, carry):
            return kahan_add(carry[0], carry[1], contribution(s))

        zeros = jnp.zeros(program.stored_result_shape, dtype=dtype)
        acc, comp = lax.fori_loop(0, num, body, (zeros, zeros))
        out = (acc + comp).reshape(program.result_shape)
        if perm is not None:
            out = jnp.transpose(out, perm)
        return scalar_fn(out), out

    diff_in = tuple(arrays[slot] for slot in wrt)
    (value_scalar, result), grads = jax.value_and_grad(
        forward, has_aux=True
    )(diff_in)
    del value_scalar
    return (
        np.asarray(result).reshape(canonical_shape),
        [np.asarray(g) for g in grads],
    )
