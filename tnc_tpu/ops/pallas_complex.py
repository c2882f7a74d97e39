"""Fused split-complex matmuls as Pallas TPU kernels.

The split-complex step kernel lowers a complex contraction to 4 real
dots (naive) or 3 dots + 5 elementwise passes (Gauss) — either way XLA
emits *separate* MXU ops whose operands each travel HBM→VMEM twice (ar
feeds two products, br feeds two products, …) plus an elementwise
epilogue over full-size outputs. This kernel computes both outputs in
one pass:

    re = arᵀ·br − aiᵀ·bi
    im = arᵀ·bi + aiᵀ·br

with each operand tile loaded into VMEM **once** per grid cell and both
accumulators living in VMEM scratch across the K loop — roughly halving
operand HBM traffic on bandwidth-bound steps and deleting the epilogue
passes entirely (docs/future_work.md item 2).

Layout: operands arrive exactly as the program compiler's dot layout
produces them — contract-dim-leading 2-D views ``A:(K, M)``,
``B:(K, N)`` (the ``cfirst`` orientation; other orientations fall back
to the plain naive path). Tile sizes respect the f32 (8, 128) minimum
and shapes must divide their tiles (program dims are powers of two, so
any dim ≥ the tile divides it; smaller/ragged shapes fall back).

Selected with ``TNC_TPU_COMPLEX_MULT=fused``; correctness is pinned in
interpret mode on CPU (tests/test_pallas_complex.py) and the kernel is
compiled for a described v5e in tests/test_v5e_compile.py. Meant to be
called inside an outer ``jax.jit`` (every executor's step kernel
already is).

This module also carries the **fused multi-step chain kernel**
(:func:`fused_chain_kl`): a run of consecutive small residual PairSteps
— grouped by :func:`tnc_tpu.ops.program.chain_groups` because each
step's output feeds the next and everything fits VMEM — executes as ONE
``pallas_call``, every intermediate living in VMEM values, so the chain
pays the per-dispatch overhead (the calibrated ``dispatch_overhead_s``
that dominates small networks) once instead of per step.

The third kernel is the **fused transpose-matmul**
(:func:`fused_transpose_dot_kl`): transpose-dominated steps normally
pay a *materialized* macro transpose (``_prep_operand``'s
``xp.transpose`` — one full HBM read + write per permuted operand)
before the dot reads the operand again. This kernel takes the operands
in their RAW stored macro views and applies the permutation in the
``BlockSpec`` index maps — each HBM tile is fetched once, already in
dot order, and streamed straight into the MXU — so the transpose pass
disappears from HBM entirely (docs/future_work.md item 2).
:func:`fused_transpose_reference` replays the identical grid with the
identical per-tile body (:func:`_transpose_tile_dot`) as plain jax
ops — the bit-parity oracle proving the kernel changed streaming
structure only.
"""

from __future__ import annotations

import math

MIN_FLOPS = 1 << 22  # below this the dispatch/grid overhead dominates

#: VMEM budget for a fused chain, in float32 elements summed over every
#: operand and intermediate the chain touches ((real, imag) pairs count
#: double, so this bounds the real VMEM bytes at 4·CHAIN_MAX_ELEMS =
#: 4 MiB of the ~16 MiB/core — generous headroom for the compiler's
#: own staging).
CHAIN_MAX_ELEMS = 1 << 20


def _tile(dim: int, cap: int, floor: int) -> int | None:
    """Largest tile ≤ cap that divides ``dim`` and is ≥ floor."""
    t = min(cap, dim)
    while t >= floor:
        if dim % t == 0:
            return t
        t //= 2
    return None


def ineligible_reason(k: int, m: int, n: int) -> str | None:
    """Why the single-step fused kernel cannot run a (K,M)x(K,N)
    problem profitably — ``None`` when it can. The reason string is the
    label the ``ops.fused_fallback`` counter and the fallback warning
    carry, so bench records say *why* fused didn't fire.

    >>> ineligible_reason(512, 1024, 1024) is None
    True
    >>> ineligible_reason(4, 4, 4)
    'flop_floor'
    >>> ineligible_reason(1024, 4, 1024)   # M below the f32 sublane tile
    'tile_floor'
    """
    if 2 * k * m * n < MIN_FLOPS:
        return "flop_floor"
    if (
        _tile(m, 128, 8) is None
        or _tile(n, 128, 128) is None
        or _tile(k, 512, 8) is None
    ):
        return "tile_floor"
    return None


def eligible(k: int, m: int, n: int) -> bool:
    """Can the fused kernel run this (K,M)x(K,N) problem profitably?

    >>> eligible(512, 1024, 1024)   # big power-of-two problem: yes
    True
    >>> eligible(4, 4, 4)           # under MIN_FLOPS and tile floors
    False
    """
    return ineligible_reason(k, m, n) is None


def fused_complex_dot_kl(ar, ai, br, bi, interpret: bool = False,
                         precision=None):
    """``(re, im)`` of the complex product ``(ar+i·ai)ᵀ · (br+i·bi)``.

    ``ar, ai: (K, M)``; ``br, bi: (K, N)``; outputs ``(M, N)`` float32.
    ``precision`` is the ``lax.Precision`` for the tile dots — callers
    on the f32 parity contract must pass HIGHEST (MXU default would run
    bf16-multiply passes and miss the 1e-5 target by orders of
    magnitude; invisible in interpret mode, which is always full f32).
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    k, m = ar.shape
    _, n = br.shape
    tm = _tile(m, 128, 8)
    tn = _tile(n, 128, 128)
    tk = _tile(k, 512, 8)
    if tm is None or tn is None or tk is None:
        raise ValueError(f"shape (K={k}, M={m}, N={n}) not tileable")

    def kernel(ar_ref, ai_ref, br_ref, bi_ref, re_ref, im_ref, racc, iacc):
        kk = pl.program_id(2)

        @pl.when(kk == 0)
        def _init():
            racc[:] = jnp.zeros_like(racc)
            iacc[:] = jnp.zeros_like(iacc)

        dims = (((0,), (0,)), ((), ()))

        def dot(x, y):
            return jax.lax.dot_general(
                x, y, dims,
                precision=precision,
                preferred_element_type=jnp.float32,
            )

        art, ait = ar_ref[:], ai_ref[:]
        brt, bit = br_ref[:], bi_ref[:]
        racc[:] += dot(art, brt) - dot(ait, bit)
        iacc[:] += dot(art, bit) + dot(ait, brt)

        @pl.when(kk == pl.num_programs(2) - 1)
        def _flush():
            re_ref[:] = racc[:]
            im_ref[:] = iacc[:]

    a_spec = pl.BlockSpec((tk, tm), lambda i, j, kk: (kk, i))
    b_spec = pl.BlockSpec((tk, tn), lambda i, j, kk: (kk, j))
    out_spec = pl.BlockSpec((tm, tn), lambda i, j, kk: (i, j))
    f32 = jnp.float32
    return pl.pallas_call(
        kernel,
        grid=(m // tm, n // tn, k // tk),
        in_specs=[a_spec, a_spec, b_spec, b_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), f32),
            jax.ShapeDtypeStruct((m, n), f32),
        ],
        scratch_shapes=_scratch((tm, tn), f32),
        interpret=interpret,
    )(ar, ai, br, bi)


def _scratch(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return [pltpu.VMEM(shape, dtype), pltpu.VMEM(shape, dtype)]


# -- fused transpose-matmul ---------------------------------------------


class OperandLayout:
    """Static HBM layout of one dot operand for the fused
    transpose-matmul kernel: how the RAW stored macro view maps onto
    the logical contract-dim-leading 2-D ``(K, F)`` matrix the dot
    consumes.

    ``view``: the stored macro view shape (the compiler's ``a_view`` /
    ``b_view``). ``k_axes`` / ``f_axes``: stored axis ids whose dims
    merge into the flat contract (``K``) and free (``F``) index, each
    listed most-significant digit first — i.e. in *permuted* order, so
    decomposing a flat index over them recovers the stored coordinates
    without ever materializing the transpose.
    """

    __slots__ = ("view", "k_axes", "f_axes")

    def __init__(self, view, k_axes, f_axes):
        self.view = tuple(int(d) for d in view)
        self.k_axes = tuple(int(a) for a in k_axes)
        self.f_axes = tuple(int(a) for a in f_axes)

    @property
    def kd(self) -> int:
        """Stored axis carrying the fastest-varying contract digit —
        the axis the k tile slides along."""
        return self.k_axes[-1]

    @property
    def fd(self) -> int:
        """Stored axis carrying the fastest-varying free digit."""
        return self.f_axes[-1]

    @property
    def k_size(self) -> int:
        return int(math.prod(self.view[a] for a in self.k_axes))

    @property
    def f_size(self) -> int:
        return int(math.prod(self.view[a] for a in self.f_axes))


def operand_layout(view, perm, dot_shape, cfirst) -> OperandLayout | None:
    """Derive an :class:`OperandLayout` from a PairStep operand's
    compiler fields, or ``None`` when the flat contract dim is not an
    exact run of permuted macro axes (``k = 1``, free side empty, or a
    contract dim straddling a fused run — none occur for
    compiler-built steps, but the gate must not trust that).

    >>> lay = operand_layout((4, 8, 128), (1, 0, 2), (8, 4, 128), True)
    >>> lay.k_axes, lay.f_axes          # k = axis 1 (dim 8), frees (4, 128)
    ((1,), (0, 2))
    >>> operand_layout((4, 8), None, (4, 8), True).k_axes
    (0,)
    >>> operand_layout((4, 8), None, (1, 32), True) is None   # k == 1
    True
    """
    view = tuple(int(d) for d in view)
    n = len(view)
    order = tuple(perm) if perm is not None else tuple(range(n))
    if sorted(order) != list(range(n)):
        return None
    k = int(dot_shape[0] if cfirst else dot_shape[-1])
    if cfirst:
        k_axes: list[int] = []
        prod = 1
        i = 0
        while prod < k and i < n:
            prod *= view[order[i]]
            k_axes.append(order[i])
            i += 1
        if prod != k:
            return None
        f_axes = list(order[i:])
    else:
        rev: list[int] = []
        prod = 1
        i = n - 1
        while prod < k and i >= 0:
            prod *= view[order[i]]
            rev.append(order[i])
            i -= 1
        if prod != k:
            return None
        k_axes = list(reversed(rev))
        f_axes = list(order[: i + 1])
    if not k_axes or not f_axes:
        return None
    return OperandLayout(view, k_axes, f_axes)


def _plan_transpose_tiles(
    a_lay: OperandLayout, b_lay: OperandLayout
) -> tuple[int, int, int] | None:
    """``(tm, tn, tk)`` tile sizes for one fused transpose-dot, or
    ``None`` when the active dims can't tile. The k tile must divide
    BOTH operands' fastest contract dims (the grid's k step covers the
    same flat-k range in each); free tiles follow the single-step
    kernel's floors (output minor dim keeps the 128-lane floor)."""
    tm = _tile(a_lay.view[a_lay.fd], 128, 8)
    tn = _tile(b_lay.view[b_lay.fd], 128, 128)
    tka = _tile(a_lay.view[a_lay.kd], 512, 8)
    tkb = _tile(b_lay.view[b_lay.kd], 512, 8)
    if tm is None or tn is None or tka is None or tkb is None:
        return None
    tk = math.gcd(tka, tkb)
    if tk < 8:
        return None
    return tm, tn, tk


def transpose_dot_ineligible_reason(
    a_lay: OperandLayout | None,
    b_lay: OperandLayout | None,
    k: int,
    m: int,
    n: int,
) -> str | None:
    """Why :func:`fused_transpose_dot_kl` cannot run this step —
    ``None`` when it can. Reason strings label the
    ``ops.fused_transpose_fallback`` counter:

    - ``layout``: a flat dim is not an exact run of permuted macro
      axes (``k = 1`` degenerates here);
    - ``flop_floor``: under :data:`MIN_FLOPS` — dispatch/grid overhead
      would dominate;
    - ``minor_axes``: the sliding tiles are not the two stored minor
      axes — leading-axis tiles would stream badly-tiled (sub-lane)
      blocks;
    - ``tile_floor``: an active dim has no tile ≥ its floor
      (non-tile-multiple perms land here).
    """
    if a_lay is None or b_lay is None:
        return "layout"
    if 2 * k * m * n < MIN_FLOPS:
        return "flop_floor"
    for lay in (a_lay, b_lay):
        nax = len(lay.view)
        if {lay.kd, lay.fd} != {nax - 2, nax - 1}:
            return "minor_axes"
    if _plan_transpose_tiles(a_lay, b_lay) is None:
        return "tile_floor"
    return None


def _transpose_tile_dot(ar, ai, br, bi, ka: int, kb: int, precision):
    """Per-tile arithmetic of the fused transpose-dot — the naive
    4-real-dot complex lowering on one (a-tile, b-tile) pair, with each
    tile in its STORED orientation (``ka``/``kb`` name the contract
    axis of each tile; the MXU takes either orientation natively).
    Shared verbatim by the Pallas kernel body and
    :func:`fused_transpose_reference`, so the kernel can only change
    streaming structure, never a bit."""
    import jax
    import jax.numpy as jnp

    dims = (((ka,), (kb,)), ((), ()))

    def dot(x, y):
        return jax.lax.dot_general(
            x, y, dims, precision=precision,
            preferred_element_type=jnp.float32,
        )

    return (
        dot(ar, br) - dot(ai, bi),
        dot(ar, bi) + dot(ai, br),
    )


def _transpose_block_geometry(a_lay, b_lay, tm, tn, tk):
    """Shared grid/block geometry: block shapes (stored order), the
    per-axis index radices each flat grid coordinate decomposes over,
    and the contract axis of each squeezed 2-D tile."""

    def one(lay, tf, tkk):
        nax = len(lay.view)
        block = [1] * nax
        block[lay.kd] = tkk
        block[lay.fd] = tf
        f_rad = [lay.view[ax] for ax in lay.f_axes[:-1]] + [
            lay.view[lay.fd] // tf
        ]
        k_rad = [lay.view[ax] for ax in lay.k_axes[:-1]] + [
            lay.view[lay.kd] // tkk
        ]
        # squeezed tile keeps the two stored-minor axes in stored order
        k_axis = 0 if lay.kd < lay.fd else 1
        tile2 = (tkk, tf) if k_axis == 0 else (tf, tkk)
        return tuple(block), f_rad, k_rad, k_axis, tile2

    return one(a_lay, tm, tk), one(b_lay, tn, tk)


def _decompose(idx, axes, radices, coords):
    """Write the mixed-radix digits of ``idx`` over ``axes`` (most
    significant first) into ``coords``. Works on python ints and traced
    scalars alike."""
    for ax, rad in zip(reversed(axes), reversed(radices)):
        coords[ax] = idx % rad
        idx = idx // rad


def fused_transpose_dot_kl(
    ar, ai, br, bi,
    a_layout: OperandLayout,
    b_layout: OperandLayout,
    interpret: bool = False,
    precision=None,
):
    """``(re, im)`` of the complex dot with BOTH operands' macro-dim
    permutations applied while streaming tiles into the MXU.

    ``ar, ai`` / ``br, bi``: the operands' RAW stored macro views
    (``a_layout.view`` / ``b_layout.view``-shaped float32 arrays — NOT
    pre-transposed). Outputs are the flat ``(M, N)`` float32 pair, rows
    iterating the first operand's free digits, columns the second's —
    exactly the prep+dot path's output order, so callers reshape to
    ``out_store`` unchanged. Each operand element crosses HBM once; the
    materialized transpose pass (read + write of the whole operand) the
    prep path pays is gone.

    Arithmetic is the naive 4-real-dot lowering accumulated in f32 VMEM
    scratch over the k grid — the same error contract as
    :func:`fused_complex_dot_kl`.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    tiles = _plan_transpose_tiles(a_layout, b_layout)
    if tiles is None:
        raise ValueError(
            f"layouts not tileable: {a_layout.view} / {b_layout.view}"
        )
    tm, tn, tk = tiles
    mm, nn = a_layout.f_size, b_layout.f_size
    kk_total = a_layout.k_size
    if b_layout.k_size != kk_total:
        raise ValueError("operand contract sizes disagree")
    (a_block, a_frad, a_krad, ka, a_tile2), (
        b_block, b_frad, b_krad, kb, b_tile2,
    ) = _transpose_block_geometry(a_layout, b_layout, tm, tn, tk)

    def a_map(i, j, kk):
        coords = [0] * len(a_block)
        _decompose(i, a_layout.f_axes, a_frad, coords)
        _decompose(kk, a_layout.k_axes, a_krad, coords)
        return tuple(coords)

    def b_map(i, j, kk):
        coords = [0] * len(b_block)
        _decompose(j, b_layout.f_axes, b_frad, coords)
        _decompose(kk, b_layout.k_axes, b_krad, coords)
        return tuple(coords)

    def kernel(ar_ref, ai_ref, br_ref, bi_ref, re_ref, im_ref, racc, iacc):
        kidx = pl.program_id(2)

        @pl.when(kidx == 0)
        def _init():
            racc[:] = jnp.zeros_like(racc)
            iacc[:] = jnp.zeros_like(iacc)

        art = ar_ref[:].reshape(a_tile2)
        ait = ai_ref[:].reshape(a_tile2)
        brt = br_ref[:].reshape(b_tile2)
        bit = bi_ref[:].reshape(b_tile2)
        dr, di = _transpose_tile_dot(art, ait, brt, bit, ka, kb, precision)
        racc[:] += dr
        iacc[:] += di

        @pl.when(kidx == pl.num_programs(2) - 1)
        def _flush():
            re_ref[:] = racc[:]
            im_ref[:] = iacc[:]

    a_spec = pl.BlockSpec(a_block, a_map)
    b_spec = pl.BlockSpec(b_block, b_map)
    out_spec = pl.BlockSpec((tm, tn), lambda i, j, kk: (i, j))
    f32 = jnp.float32
    return pl.pallas_call(
        kernel,
        grid=(mm // tm, nn // tn, kk_total // tk),
        in_specs=[a_spec, a_spec, b_spec, b_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((mm, nn), f32),
            jax.ShapeDtypeStruct((mm, nn), f32),
        ],
        scratch_shapes=_scratch((tm, tn), f32),
        interpret=interpret,
    )(ar, ai, br, bi)


def fused_transpose_reference(
    ar, ai, br, bi,
    a_layout: OperandLayout,
    b_layout: OperandLayout,
    precision=None,
):
    """The fused transpose-dot as plain jax ops — no ``pallas_call``.

    Replays the kernel's exact grid: extracts the SAME stored-order
    blocks the ``BlockSpec`` index maps would fetch, squeezes them to
    the same 2-D tiles, runs the same shared per-tile body
    (:func:`_transpose_tile_dot`) and accumulates k tiles in the same
    ascending order — bit-identical by construction, so the interpret-
    mode parity tests prove the kernel moved streaming structure only.
    Python-looped over the grid: an oracle for tests and smokes, not an
    execution path.
    """
    import jax.numpy as jnp

    tiles = _plan_transpose_tiles(a_layout, b_layout)
    if tiles is None:
        raise ValueError("layouts not tileable")
    tm, tn, tk = tiles
    mm, nn = a_layout.f_size, b_layout.f_size
    kk_total = a_layout.k_size
    (a_block, a_frad, a_krad, ka, a_tile2), (
        b_block, b_frad, b_krad, kb, b_tile2,
    ) = _transpose_block_geometry(a_layout, b_layout, tm, tn, tk)

    def block(arr, lay, blk, frad, krad, fidx, kidx, tile2):
        coords = [0] * len(blk)
        _decompose(fidx, lay.f_axes, frad, coords)
        _decompose(kidx, lay.k_axes, krad, coords)
        sl = tuple(
            slice(c * b, (c + 1) * b) for c, b in zip(coords, blk)
        )
        return arr[sl].reshape(tile2)

    out_r = jnp.zeros((mm, nn), dtype=jnp.float32)
    out_i = jnp.zeros((mm, nn), dtype=jnp.float32)
    for i in range(mm // tm):
        for j in range(nn // tn):
            racc = jnp.zeros((tm, tn), dtype=jnp.float32)
            iacc = jnp.zeros((tm, tn), dtype=jnp.float32)
            for kidx in range(kk_total // tk):
                art = block(ar, a_layout, a_block, a_frad, a_krad, i, kidx, a_tile2)
                ait = block(ai, a_layout, a_block, a_frad, a_krad, i, kidx, a_tile2)
                brt = block(br, b_layout, b_block, b_frad, b_krad, j, kidx, b_tile2)
                bit = block(bi, b_layout, b_block, b_frad, b_krad, j, kidx, b_tile2)
                dr, di = _transpose_tile_dot(
                    art, ait, brt, bit, ka, kb, precision
                )
                racc = racc + dr
                iacc = iacc + di
            out_r = out_r.at[i * tm:(i + 1) * tm, j * tn:(j + 1) * tn].set(racc)
            out_i = out_i.at[i * tm:(i + 1) * tm, j * tn:(j + 1) * tn].set(iacc)
    return out_r, out_i


# -- fused multi-step residual chains -----------------------------------


class ChainLink:
    """Static metadata for one follow-on step of a fused chain: how the
    carried value (the previous step's output, a 2-D VMEM array) enters
    this step's dot against its pre-prepped ``(K, X)`` operand.

    ``carried_shape``: the 2-D matrix the flat carried value regroups
    to (a pure row-major reshape — :func:`tnc_tpu.ops.program.
    chain_groups` only admits steps whose carried operand needs no
    transpose). ``k_axis``: which axis of that matrix is the contract
    dim (0 = contract-first, 1 = contract-last). ``carried_first``:
    whether the carried value is the dot's first operand (its free axis
    supplies the output rows) — the PairStep ``swap`` folded out.
    """

    __slots__ = ("carried_first", "carried_shape", "k_axis")

    def __init__(
        self,
        carried_first: bool,
        carried_shape: tuple[int, int],
        k_axis: int,
    ):
        self.carried_first = bool(carried_first)
        self.carried_shape = (int(carried_shape[0]), int(carried_shape[1]))
        self.k_axis = int(k_axis)

    def out_shape(self, link_free: int) -> tuple[int, int]:
        free = self.carried_shape[1 - self.k_axis]
        if self.carried_first:
            return (free, link_free)
        return (link_free, free)


def chain_out_shape(
    m0: int, n0: int, links, link_frees
) -> tuple[int, int]:
    """Final 2-D output shape of a chain starting at ``(m0, n0)``."""
    shape = (m0, n0)
    for link, free in zip(links, link_frees):
        shape = link.out_shape(free)
    return shape


def _chain_compute(vals, links, precision):
    """The chain's arithmetic on plain arrays — shared verbatim by the
    Pallas kernel body (on VMEM-loaded values) and the bit-parity
    reference (:func:`fused_chain_reference`), so the only thing the
    kernel can add is dispatch fusion, never a numerical deviation."""
    import jax

    def cdot(xr, xi, yr, yi, xk, yk):
        dims = (((xk,), (yk,)), ((), ()))

        def dot(x, y):
            # accumulate in the operand dtype (f32 on the MXU path;
            # float64 split pairs — the complex128 CPU oracle — must
            # NOT downcast through the chain)
            return jax.lax.dot_general(
                x, y, dims,
                precision=precision,
                preferred_element_type=x.dtype,
            )

        return (
            dot(xr, yr) - dot(xi, yi),
            dot(xr, yi) + dot(xi, yr),
        )

    zr, zi = cdot(vals[0], vals[1], vals[2], vals[3], 0, 0)
    for i, link in enumerate(links):
        cr = vals[4 + 2 * i]
        ci = vals[5 + 2 * i]
        zr = zr.reshape(link.carried_shape)
        zi = zi.reshape(link.carried_shape)
        if link.carried_first:
            zr, zi = cdot(zr, zi, cr, ci, link.k_axis, 0)
        else:
            zr, zi = cdot(cr, ci, zr, zi, 0, link.k_axis)
    return zr, zi


def fused_chain_reference(first_ops, link_ops, links, precision=None):
    """The chain computation as plain jax ops — no ``pallas_call``.
    The bit-parity oracle for the interpret-mode tests: the kernel
    must produce the identical bits, proving fusion changed dispatch
    structure only."""
    vals = list(first_ops)
    for cr, ci in link_ops:
        vals.extend((cr, ci))
    return _chain_compute(vals, links, precision)


def fused_chain_kl(
    first_ops,
    link_ops,
    links,
    interpret: bool = False,
    precision=None,
):
    """Execute a whole residual chain as ONE Pallas dispatch.

    ``first_ops = (fr, fi, sr, si)``: the head step's two operands,
    pre-prepped to contract-dim-leading 2-D ``(K0, M0)`` / ``(K0, N0)``
    float32 arrays, already in dot order (``swap`` folded out by the
    caller). ``link_ops = [(cr, ci), ...]``: each follow-on step's
    non-carried operand, pre-prepped to ``(K_i, X_i)``. ``links``: one
    :class:`ChainLink` per follow-on step.

    Every array is a full-array VMEM block (no grid): the chain-grouping
    pass only admits runs whose combined operands and intermediates fit
    :data:`CHAIN_MAX_ELEMS`, so small residual steps stream through VMEM
    values with a single HBM round-trip at the chain boundary — the
    chain pays one dispatch overhead instead of ``len(links) + 1``.
    Arithmetic is the naive 4-real-dot complex lowering (same error
    contract as the single-step fused kernel).

    Returns the chain's final ``(re, im)`` 2-D float32 pair.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    fr, fi, sr, si = first_ops
    n_links = len(links)
    if n_links != len(link_ops):
        raise ValueError("links and link_ops must pair up")

    def kernel(*refs):
        ins, outs = refs[: 4 + 2 * n_links], refs[4 + 2 * n_links:]
        zr, zi = _chain_compute(
            [r[:] for r in ins], links, precision
        )
        outs[0][:] = zr
        outs[1][:] = zi

    out_shape = chain_out_shape(
        fr.shape[1], sr.shape[1], links, [c[0].shape[1] for c in link_ops]
    )
    flat_ins = [fr, fi, sr, si]
    for cr, ci in link_ops:
        flat_ins.extend((cr, ci))
    out_dtype = jnp.asarray(fr).dtype  # f32 device path; f64 oracle
    return pl.pallas_call(
        kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM) for _ in flat_ins
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(out_shape, out_dtype),
            jax.ShapeDtypeStruct(out_shape, out_dtype),
        ],
        interpret=interpret,
    )(*flat_ins)
