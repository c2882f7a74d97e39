"""Approximate contraction: boundary-MPS with SVD truncation.

The reference lists approximate contraction as future work
(``book/src/future_work.md``); this module implements the standard
boundary-MPS scheme for 2-D grid networks (PEPS sandwiches, and the
qubit×depth grids :mod:`tnc_tpu.approx.program` flattens circuits
into): the top row is an MPS, every interior row an MPO; after each
MPS·MPO application the boundary MPS is compressed to bond dimension
``chi`` by a QR canonicalization sweep followed by truncated SVDs.
Memory and time are then polynomial in ``chi`` instead of exponential
in the grid width — the classic accuracy-for-cost dial exact
contraction lacks.

Beyond the value, every sweep reports its **accumulated discarded SVD
weight** (:func:`boundary_contract_with_weight`) — the sum over all
truncations of the relative discarded singular-value mass. Zero weight
means nothing was truncated and the sweep is exact (up to roundoff);
the :mod:`tnc_tpu.approx.ladder` chi-ladder turns the weight plus
inter-rung deltas into a per-answer error estimate.

Scope notes:

- Sites may be connected by *several* parallel bonds (a PEPS sandwich
  has one bond per layer between neighbours); bonds per direction are
  fused into one dense axis, neighbours aligned by sorted leg id.
- The linear algebra runs through numpy at complex128 (QR/SVD of
  χ-sized matrices — planner-scale host work, like pathfinding; the
  contraction dial is what matters on TPU: pick ``chi`` so the exact
  *sliced* plan of the compressed network fits, or use the boundary
  value directly).
- ``backend="jax"`` streams the sweep row by row through a per-row
  jitted apply+compress step (cached per (shapes, chi)), so only ONE
  interior row's dense site tensors are materialized at a time — the
  documented one-row-alive memory bound holds on both backends.
- ``collapse_peps_sandwich`` flattens the ``builders.peps`` sandwich
  (layer-major ordering, ``peps.rs:446-460`` equivalent) into the
  single-layer grid this module consumes.
"""

from __future__ import annotations

import functools as _functools
from typing import Sequence

import numpy as np

from tnc_tpu import obs
from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor
from tnc_tpu.tensornetwork.tensordata import TensorData

#: accumulated relative discarded weight below this is roundoff, not
#: truncation — the sweep computed the closed network exactly (the
#: chi-ladder reports err ≈ 0 at such rungs)
EXACT_WEIGHT = 1e-20

#: complex128 element width (the bytes side of the sweep's roofline)
_ELEM_BYTES = 16


def _site_array(t: LeafTensor) -> np.ndarray:
    return np.asarray(t.data.into_data(), dtype=np.complex128).reshape(
        t.shape
    )


def _grouped(t: LeafTensor, groups: Sequence[Sequence[int]]) -> np.ndarray:
    """Dense site tensor with axes permuted/fused to the leg groups
    (one fused axis per group, legs within a group in the given order;
    missing groups become dim-1 axes)."""
    arr = _site_array(t)
    pos = {leg: i for i, leg in enumerate(t.legs)}
    perm: list[int] = []
    shape: list[int] = []
    for group in groups:
        size = 1
        for leg in group:
            perm.append(pos[leg])
            size *= t.bond_dims[pos[leg]]
        shape.append(size)
    if len(perm) != len(t.legs):
        raise ValueError(
            f"site tensor has legs {sorted(t.legs)} outside its grid "
            f"neighbourhood {sorted(l for g in groups for l in g)}"
        )
    return np.transpose(arr, perm).reshape(shape)


def _grid_groups(grid) -> list[list[tuple[list, list, list, list]]]:
    """Per-site ``(left, right, up, down)`` leg groups of a rectangular
    grid (shared validation for the contractor and the geometry/cost
    helpers)."""
    rows = len(grid)
    if rows < 2 or any(len(r) != len(grid[0]) for r in grid):
        raise ValueError("grid must be rectangular with >= 2 rows")
    cols = len(grid[0])
    if cols < 1:
        raise ValueError("grid rows must be non-empty")
    legs_of = [[set(t.legs) for t in row] for row in grid]

    def shared(r1, c1, r2, c2) -> list[int]:
        if 0 <= r2 < rows and 0 <= c2 < cols:
            return sorted(legs_of[r1][c1] & legs_of[r2][c2])
        return []

    return [
        [
            (
                shared(r, c, r, c - 1),   # left
                shared(r, c, r, c + 1),   # right
                shared(r, c, r - 1, c),   # up
                shared(r, c, r + 1, c),   # down
            )
            for c in range(cols)
        ]
        for r in range(rows)
    ]


def grid_site_dims(grid) -> list[list[tuple[int, int, int, int]]]:
    """Per-site fused ``(left, right, up, down)`` bond dims — the
    geometry the closed-form sweep cost model
    (:mod:`tnc_tpu.approx.cost`) walks without materializing any site
    data.

    >>> import numpy as np
    >>> from tnc_tpu.builders.peps import peps
    >>> rng = np.random.default_rng(0)
    >>> tn = attach_random_data(peps(3, 3, 2, 2, 0), rng)
    >>> grid = collapse_peps_sandwich(tn, 3, 3, 0)
    >>> grid_site_dims(grid)[1][1]  # interior site of a vd=2 sandwich
    (4, 4, 4, 4)
    """
    groups = _grid_groups(grid)
    out: list[list[tuple[int, int, int, int]]] = []
    for row, grow in zip(grid, groups):
        dims_row = []
        for t, site_groups in zip(row, grow):
            dim_of = dict(zip(t.legs, t.bond_dims))
            dims_row.append(
                tuple(
                    int(np.prod([dim_of[l] for l in g], initial=1))
                    for g in site_groups
                )
            )
        out.append(dims_row)
    return out


def _truncated_svd(m, chi: int, cutoff: float, xp=np):
    """Truncated SVD plus the **relative discarded weight** (discarded
    singular mass over total; 0.0 when nothing real was cut)."""
    u, s, vh = xp.linalg.svd(m, full_matrices=False)
    if xp is np:
        keep = int(np.sum(s > cutoff * (s[0] if s.size else 1.0)))
        keep = max(1, min(keep, chi))
        total = float(np.sum(s * s))
        disc = float(np.sum(s[keep:] * s[keep:]))
        rel = disc / total if total > 0.0 else 0.0
    else:
        # jitted path: the kept rank must be static, so the cut is by
        # chi alone (cutoff-based rank is value-dependent)
        keep = max(1, min(int(s.shape[0]), chi))
        total = xp.sum(s * s)
        disc = xp.sum(s[keep:] * s[keep:])
        rel = xp.where(total > 0.0, disc / total, 0.0)
    return u[:, :keep], s[:keep], vh[:keep], rel


def _compress_mps(mps, chi: int, cutoff: float, xp=np):
    """Canonicalize left-to-right (QR), then truncate right-to-left
    (SVD). Tensors are (Dl, d, Dr). Returns ``(mps, weight)`` where
    ``weight`` is the summed relative discarded SVD weight."""
    mps = list(mps)
    n = len(mps)
    weight = 0.0
    # left-to-right QR: left-canonical form
    for i in range(n - 1):
        dl, d, dr = mps[i].shape
        q, r = xp.linalg.qr(mps[i].reshape(dl * d, dr))
        mps[i] = q.reshape(dl, d, q.shape[1])
        mps[i + 1] = xp.tensordot(r, mps[i + 1], axes=(1, 0))
    # right-to-left truncated SVD
    for i in range(n - 1, 0, -1):
        dl, d, dr = mps[i].shape
        u, s, vh, rel = _truncated_svd(
            mps[i].reshape(dl, d * dr), chi, cutoff, xp
        )
        weight = weight + rel
        mps[i] = vh.reshape(vh.shape[0], d, dr)
        carry = u * s  # (dl, keep)
        mps[i - 1] = xp.tensordot(mps[i - 1], carry, axes=(2, 0))
    return mps, weight


def _apply_mpo(mps, mpo, xp=np):
    """MPS (Dl, d_up, Dr) x MPO (Wl, Wr, d_up, d_down) →
    fat MPS (Dl·Wl, d_down, Dr·Wr)."""
    out = []
    for a, w in zip(mps, mpo):
        dl, dup, dr = a.shape
        wl, wr, wup, wdown = w.shape
        if dup != wup:
            raise ValueError(f"vertical bond mismatch: {dup} vs {wup}")
        t = xp.tensordot(a, w, axes=(1, 2))  # (dl, dr, wl, wr, wdown)
        t = xp.transpose(t, (0, 2, 4, 1, 3))  # (dl, wl, wdown, dr, wr)
        out.append(t.reshape(dl * wl, wdown, dr * wr))
    return out


def _apply_compress(xp, mps, mpo, chi: int, cutoff: float):
    mps = _apply_mpo(mps, mpo, xp)
    return _compress_mps(mps, chi, cutoff, xp)


def _close(xp, mps, bottom):
    env = xp.ones((1, 1), dtype=mps[0].dtype)
    for a, site in zip(mps, bottom):
        # env (Dl, Bl) · a (Dl, d, Dr) · site (Bl, d, Br) -> (Dr, Br)
        tmp = xp.tensordot(env, a, axes=(0, 0))  # (Bl, d, Dr)
        env = xp.tensordot(tmp, site, axes=((0, 1), (0, 1)))
    return env


def row_cost(
    mps_shapes: Sequence[tuple], mpo_shapes: Sequence[tuple], chi: int
) -> tuple[float, float, int, list[tuple]]:
    """Leading-order cost of ONE apply+compress boundary step:
    ``(flops, bytes, ops, out_shapes)``.

    Flops are naive complex multiply-add counts (the same ``k·m·n``
    convention as :func:`tnc_tpu.ops.program.step_flops`, so
    :class:`~tnc_tpu.obs.calibrate.CalibratedCostModel` prices them in
    the domain it was fitted in); QR is counted as ``2·m·n·min`` and
    SVD as ``4·m·n·min``. ``bytes`` is the complex128 traffic of every
    operand read and result written; ``ops`` the dispatched linalg
    calls (the cost model's per-dispatch overhead multiplier);
    ``out_shapes`` the compressed boundary shapes, so a caller can walk
    a whole sweep row by row without materializing data
    (:func:`tnc_tpu.approx.cost.sweep_cost`)."""
    flops = 0.0
    elems = 0.0
    ops = 0
    shapes: list[tuple] = []
    for (dl, d, dr), (wl, wr, wup, wdown) in zip(mps_shapes, mpo_shapes):
        if d != wup:
            raise ValueError(f"vertical bond mismatch: {d} vs {wup}")
        flops += float(dl) * dr * d * wl * wr * wdown
        elems += dl * d * dr + wl * wr * wup * wdown
        elems += dl * wl * wdown * dr * wr
        ops += 1
        shapes.append((dl * wl, wdown, dr * wr))
    n = len(shapes)
    # left-to-right QR canonicalization
    for i in range(n - 1):
        dl, d, dr = shapes[i]
        m, k = dl * d, dr
        r = min(m, k)
        flops += 2.0 * m * k * r
        elems += m * k + m * r + r * k
        ops += 1
        shapes[i] = (dl, d, r)
        dl2, d2, dr2 = shapes[i + 1]
        flops += float(r) * k * d2 * dr2
        elems += r * k + k * d2 * dr2 + r * d2 * dr2
        ops += 1
        shapes[i + 1] = (r, d2, dr2)
    # right-to-left truncated SVD
    for i in range(n - 1, 0, -1):
        dl, d, dr = shapes[i]
        m, k = dl, d * dr
        r = min(m, k, chi)
        flops += 4.0 * m * k * min(m, k)
        elems += m * k + m * r + r * k
        ops += 1
        shapes[i] = (r, d, dr)
        dl0, d0, dr0 = shapes[i - 1]
        flops += float(dl0) * d0 * dr0 * r
        elems += dl0 * d0 * dr0 + dr0 * r + dl0 * d0 * r
        ops += 1
        shapes[i - 1] = (dl0, d0, r)
    return flops, elems * _ELEM_BYTES, ops, shapes


def close_cost(
    mps_shapes: Sequence[tuple], bottom_shapes: Sequence[tuple]
) -> tuple[float, float, int]:
    """Leading-order cost ``(flops, bytes, ops)`` of contracting the
    final boundary MPS against the bottom row."""
    flops = 0.0
    elems = 0.0
    ops = 0
    eb = 1
    for (dl, d, dr), (bl, bd, br) in zip(mps_shapes, bottom_shapes):
        # env (dl, eb) · a (dl, d, dr): k=dl, out (eb, d, dr)
        flops += float(eb) * dl * d * dr
        # tmp (eb, d, dr) · site (eb==bl, d, br): k=eb·d, out (dr, br)
        flops += float(eb) * d * dr * br
        elems += dl * eb + dl * d * dr + bl * bd * br + dr * br
        ops += 2
        eb = br
    return flops, elems * _ELEM_BYTES, ops


def _sweep_numpy(top, mid_rows, bottom, chi: int, cutoff: float):
    """Host sweep: one interior row's grouped site tensors alive at a
    time, one ``approx.row`` span per row carrying the row's
    closed-form flop/byte counts."""
    mps = list(top)
    weight = 0.0
    for r, mpo in enumerate(mid_rows, start=1):
        flops, nbytes, _ops, _shapes = row_cost(
            [a.shape for a in mps], [w.shape for w in mpo], chi
        )
        with obs.span("approx.row", row=r, chi=chi) as sp:
            mps, w = _apply_compress(np, mps, mpo, chi, cutoff)
            sp.add(flops=flops, bytes=nbytes)
        weight += float(w)
    env = _close(np, mps, bottom)
    return env, weight


@_functools.lru_cache(maxsize=256)
def _jax_row_fn(chi: int, mps_shapes: tuple, mpo_shapes: tuple):
    """One jitted apply+compress step per (shapes, chi) — the
    streaming sweep's unit of compilation. Distinct rows of one grid
    that share shapes (the steady state of a deep circuit grid) share
    one executable; repeat calls over same-geometry grids recompile
    nothing."""
    import jax

    def run(mps, mpo):
        import jax.numpy as jnp

        return _apply_compress(jnp, list(mps), list(mpo), chi, 0.0)

    return jax.jit(run)


@_functools.lru_cache(maxsize=64)
def _jax_close_fn(mps_shapes: tuple, bottom_shapes: tuple):
    import jax

    def run(mps, bottom):
        import jax.numpy as jnp

        return _close(jnp, list(mps), list(bottom))

    return jax.jit(run)


def _sweep_jax(top_fn, mid_iter, bottom_fn, chi: int):
    """Streaming device sweep: rows are grouped, transferred and
    consumed ONE AT A TIME (the same one-row-alive bound as the numpy
    path — materializing every row up front would defeat it on exactly
    the tall grids that need the boundary scheme), each through the
    per-(shapes, chi) jitted apply+compress step."""
    import jax

    # Complex QR/SVD only exists on CPU-like backends (the TPU path
    # of this stack is split-complex and has no complex dtypes), so
    # the sweep is pinned to the CPU platform explicitly — on an
    # accelerator-default environment the default device would be
    # the TPU and the program could not lower.
    cpu = jax.local_devices(backend="cpu")[0]
    dtype = (
        "complex128" if jax.config.read("jax_enable_x64") else "complex64"
    )

    def put_row(row):
        return [
            jax.device_put(np.asarray(a, dtype=dtype), cpu) for a in row
        ]

    with jax.default_device(cpu):
        mps = put_row(top_fn())
        weights = []
        for r, row in enumerate(mid_iter, start=1):
            mpo = put_row(row)
            mps_shapes = tuple(tuple(a.shape) for a in mps)
            mpo_shapes = tuple(tuple(w.shape) for w in mpo)
            flops, nbytes, _ops, _shapes = row_cost(
                mps_shapes, mpo_shapes, chi
            )
            with obs.span("approx.row", row=r, chi=chi) as sp:
                mps, w = _jax_row_fn(chi, mps_shapes, mpo_shapes)(mps, mpo)
                sp.add(flops=flops, bytes=nbytes)
            weights.append(w)
        bottom = put_row(bottom_fn())
        env = _jax_close_fn(
            tuple(tuple(a.shape) for a in mps),
            tuple(tuple(b.shape) for b in bottom),
        )(mps, bottom)
        weight = float(sum(float(np.asarray(w)) for w in weights))
    return np.asarray(env), weight


def boundary_contract_with_weight(
    grid: Sequence[Sequence[LeafTensor]],
    chi: int,
    cutoff: float = 0.0,
    backend: str = "numpy",
) -> tuple[complex, float]:
    """Contract a closed 2-D grid network approximately, returning
    ``(value, weight)`` where ``weight`` is the sweep's accumulated
    relative discarded SVD mass — ``0.0`` (or roundoff below
    :data:`EXACT_WEIGHT`) means no truncation happened and the value is
    exact up to floating point. The whole sweep runs under an
    ``approx.sweep`` obs span with per-row ``approx.row`` children
    carrying closed-form flop/byte counters."""
    rows = len(grid)
    groups = _grid_groups(grid)
    cols = len(grid[0])
    if chi < 1:
        raise ValueError("chi must be >= 1")
    if backend not in ("numpy", "jax"):
        raise ValueError(f"unknown backend {backend!r}")
    if backend == "jax" and cutoff:
        raise ValueError(
            "cutoff-based rank is value-dependent; the jitted jax sweep "
            "supports chi truncation only"
        )

    def top_row():
        out = []
        for c in range(cols):
            left, right, up, down = groups[0][c]
            if up:
                raise ValueError("top row must have no upward bonds")
            out.append(_grouped(grid[0][c], (left, down, right)))
        return out

    def mid_rows():
        # lazy per row: only one interior row's dense grouped copies are
        # alive at a time (both backends — the jax path streams rows
        # through the per-row jitted step)
        for r in range(1, rows - 1):
            yield [
                _grouped(grid[r][c], groups[r][c]) for c in range(cols)
            ]

    def bottom_row():
        out = []
        for c in range(cols):
            left, right, up, down = groups[rows - 1][c]
            if down:
                raise ValueError("bottom row must have no downward bonds")
            out.append(_grouped(grid[rows - 1][c], (left, up, right)))
        return out

    with obs.span(
        "approx.sweep", rows=rows, cols=cols, chi=chi, backend=backend
    ):
        if backend == "jax":
            env, weight = _sweep_jax(top_row, mid_rows(), bottom_row, chi)
        else:
            env, weight = _sweep_numpy(
                top_row(), mid_rows(), bottom_row(), chi, cutoff
            )
    if env.shape != (1, 1):
        raise ValueError("grid did not close to a scalar")
    return complex(env[0, 0]), float(weight)


def boundary_mps_contract(
    grid: Sequence[Sequence[LeafTensor]],
    chi: int,
    cutoff: float = 0.0,
    backend: str = "numpy",
) -> complex:
    """Contract a closed 2-D grid network approximately.

    ``grid[r][c]`` are data-carrying leaf tensors whose legs connect
    only to the four lattice neighbours (parallel bonds allowed, fused
    per direction). ``chi`` caps the boundary-MPS bond dimension; with
    ``chi`` at least the exact boundary rank the result is exact.

    ``backend="jax"`` runs each boundary step as a jitted XLA program,
    explicitly pinned to the CPU platform (complex QR/SVD has no TPU
    lowering in this stack — the TPU execution path is split-complex):
    every intermediate shape is static given the grid, so compiled row
    steps are cached per (shapes, chi) and reused across rows AND
    calls, while rows stream through one at a time. The static-rank
    constraint means the value-dependent ``cutoff`` is numpy-only.

    >>> import numpy as np
    >>> from tnc_tpu.builders.peps import peps
    >>> rng = np.random.default_rng(7)
    >>> tn = attach_random_data(peps(3, 3, 2, 2, 1), rng)
    >>> from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    >>> from tnc_tpu.tensornetwork.contraction import contract_tensor_network
    >>> path = Greedy(OptMethod.GREEDY).find_path(tn).replace_path()
    >>> want = complex(contract_tensor_network(tn, path,
    ...     backend="numpy").data.into_data().reshape(-1)[0])
    >>> grid = collapse_peps_sandwich(tn, 3, 3, 1)
    >>> got = boundary_mps_contract(grid, chi=4096)  # chi >= exact rank
    >>> abs(got - want) <= 1e-8 * max(1.0, abs(want))
    True
    """
    value, _weight = boundary_contract_with_weight(
        grid, chi, cutoff=cutoff, backend=backend
    )
    return value


def collapse_peps_sandwich(
    tn: CompositeTensor, length: int, depth: int, layers: int
) -> list[list[LeafTensor]]:
    """Flatten a ``builders.peps`` sandwich (data attached) into the
    single-layer ``depth × length`` grid ``boundary_mps_contract``
    consumes: each site's ``layers + 2`` stacked tensors are contracted
    over their vertical physical bonds (greedy local path), leaving the
    per-layer horizontal bonds as parallel grid bonds. A failure inside
    one site's local contraction (wrong attached data shape, broken
    bonds) is re-raised naming the offending site ``(row, col)``."""
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.tensornetwork.contraction import contract_tensor_network

    n_layers = layers + 2
    leaves = list(tn.tensors)
    if len(leaves) != n_layers * depth * length:
        raise ValueError(
            f"expected {n_layers * depth * length} tensors "
            f"(layer-major peps ordering), got {len(leaves)}"
        )

    def site_index(k, r, c):
        return k * depth * length + r * length + c

    grid: list[list[LeafTensor]] = []
    with obs.span(
        "approx.collapse", length=length, depth=depth, layers=layers
    ):
        for r in range(depth):
            row = []
            for c in range(length):
                stack = CompositeTensor(
                    [
                        leaves[site_index(k, r, c)].copy()
                        for k in range(n_layers)
                    ]
                )
                try:
                    result = Greedy(OptMethod.GREEDY).find_path(stack)
                    merged = contract_tensor_network(
                        stack, result.replace_path(), backend="numpy"
                    )
                except Exception as exc:
                    raise ValueError(
                        f"collapse_peps_sandwich: site (row {r}, col {c}) "
                        f"failed to contract its {n_layers}-layer stack "
                        f"({type(exc).__name__}: {exc})"
                    ) from exc
                row.append(merged)
            grid.append(row)
    return grid


def attach_random_data(
    tn: CompositeTensor, rng: np.random.Generator, scale: float | None = None
) -> CompositeTensor:
    """Fill every metadata-only leaf with seeded complex Gaussian data
    (builder networks like ``peps`` are metadata-only); leaves that
    already carry data (gates, matrices, file refs) are left untouched
    after validating that their payload matches the leaf's declared
    shape — a mismatch is reported naming the offending leaf and both
    shapes, not as a downstream reshape error. ``scale`` defaults to
    per-tensor ``1/sqrt(size)`` so contractions stay O(1)."""
    from tnc_tpu.tensornetwork.tensordata import DataKind

    with obs.span("approx.attach_data", leaves=len(tn.tensors)):
        for i, leaf in enumerate(tn.tensors):
            if isinstance(leaf, CompositeTensor):
                attach_random_data(leaf, rng, scale)
                continue
            if leaf.data.kind is not DataKind.NONE:
                have = int(np.asarray(leaf.data.into_data()).size)
                want = int(np.prod(leaf.shape, initial=1))
                if have != want:
                    raise ValueError(
                        f"attach_random_data: leaf {i} (legs "
                        f"{list(leaf.legs)}) carries data of {have} "
                        f"elements but its declared shape {leaf.shape} "
                        f"needs {want}"
                    )
                continue
            shape = leaf.shape
            s = scale if scale is not None else 1.0 / np.sqrt(
                max(1.0, float(np.prod(shape)))
            )
            data = (
                rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
            ) * s
            leaf.data = TensorData.matrix(data.astype(np.complex128))
    return tn
