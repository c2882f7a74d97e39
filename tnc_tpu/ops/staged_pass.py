"""The staged prep of a large streamed operand as ONE Pallas pass.

A staged plan (:func:`tnc_tpu.ops.program._staged_ops`, ended by
:func:`tnc_tpu.ops.program.tiled_prep_ops` in the tiled image ``(M /
128, 2k, 128)``) is a row transpose, one static permutation of a
``W``-wide lane window (``lanemix``) and a second row transpose. Where
every digit of it is a power of two the whole plan permutes the BITS of
the flat element index: output bit ``o`` holds input bit ``pi[o]``
(:func:`bit_permutation`). This module runs that permutation as one
``pallas_call`` that reads each element of the stored buffer once and
writes the image once, with no MXU pass and no gather:

- the bits of a VMEM block: the 7 lane bits and 3 sublane bits of an
  ``(8, 128)`` tile on either side, the input bits that land in the
  output's tile (``c`` of them, the same number of output bits come from
  the input's tile) as the index of a vreg in a small static set, and
  runs of bits that keep their order on both sides as loop indices;
- inside the block, the bits that cross between lanes, sublanes and
  vregs are exchanged two at a time (:func:`swap_plan`): a lane or
  sublane bit with a vreg bit by two ``pltpu.roll``\\ s and two
  ``lax.select``\\ s over a pair of vregs, two lane (or two sublane)
  bits by two rolls and two selects over each vreg; every other bit
  moves through the ``BlockSpec`` index maps.

Data is only moved, so the image is the one-hot matmul's bit for bit.
Whether a staged prep takes the pass is read from its shape alone
(:func:`pass_plan`), before any op is traced; nothing catches a kernel
error and falls back. A kernel is traced and lowered in every process,
so the rule leaves small operands to the matmul (:data:`MIN_PASS_ELEMS`).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

LANE_BITS = 7  # 128 lanes
SUBLANE_BITS = 3  # 8 sublanes of an f32 vreg
TILE_BITS = LANE_BITS + SUBLANE_BITS
#: bits of one block: 2^18 f32 elements, 1 MiB in and 1 MiB out, each
#: double-buffered by the pipeline
BLOCK_BITS = 18
#: vregs a loop body holds (the static set times the unrolled loop
#: steps): enough independent rolls to hide their latency
VREGS_PER_BODY = 64
#: the widest lane window the pass takes: the widest of the slice plans
#: the benchmark runs (PERF.md, section 4)
MAX_WINDOW = 2048
#: the fewest elements (both planes) of an operand the pass takes. A
#: kernel is traced and lowered again in every process (the compile cache
#: keeps only the compiled program): some 1-3 s of host time a kernel at
#: a slice cell's set-up on the chip's host, where the one-hot path costs
#: none (PERF.md, section 6). The operands of 2^24 elements hold
#: most of what the pass saves (58 % of the staged elements of
#: ``amp_slices``) with 5 of its 20 kernels.
MIN_PASS_ELEMS = 1 << 24


def _log2(x: int) -> int | None:
    """``log2(x)`` for a power of two, else ``None``."""
    x = int(x)
    if x < 1 or x & (x - 1):
        return None
    return x.bit_length() - 1


@functools.lru_cache(maxsize=4096)
def bit_permutation(n_elems: int, ops: tuple) -> tuple[int, ...] | None:
    """The staged ``ops`` (reshapes, transposes, ``("lanemix", W,
    idx)``) on a buffer of ``n_elems`` elements as a permutation of the
    bits of the flat index: ``pi[o]`` is the input bit that output bit
    ``o`` holds (bit 0 the least significant). ``None`` where a dimension
    is no power of two or the lane table permutes no bits."""
    n = _log2(n_elems)
    if n is None:
        return None
    flat = list(range(n - 1, -1, -1))  # the input bit of each place, msb first
    axes = [flat]
    for op in ops:
        if op[0] == "reshape":
            flat = [b for axis in axes for b in axis]
            axes = []
            for dim in op[1]:
                width = _log2(dim)
                if width is None:
                    return None
                axes.append(flat[:width])
                flat = flat[width:]
            if flat:
                return None
        elif op[0] == "transpose":
            axes = [axes[a] for a in op[1]]
        else:  # ("lanemix", W, idx): out[..., j] = in[..., idx[j]]
            w = _log2(op[1])
            if w is None:
                return None
            idx = np.asarray(op[2], dtype=np.int64)
            sigma = []
            for b in range(w):
                v = int(idx[1 << b])
                if _log2(v) is None:
                    return None
                sigma.append(v.bit_length() - 1)
            j = np.arange(1 << w, dtype=np.int64)
            want = np.zeros_like(j)
            for b, s in enumerate(sigma):
                want |= ((j >> b) & 1) << s
            if not np.array_equal(idx, want):
                return None
            flat = [b for axis in axes for b in axis]
            window = flat[len(flat) - w:]  # msb first
            old = window[::-1]  # window bit position -> input bit
            new = [old[sigma[b]] for b in range(w)]
            flat = flat[: len(flat) - w] + new[::-1]
            axes = [flat]
    flat = [b for axis in axes for b in axis]
    return tuple(flat[n - 1 - o] for o in range(n))


def swap_plan(pi: tuple[int, ...]) -> tuple[tuple, tuple]:
    """``(r_in, swaps)`` of a block. ``r_in``: the input bits above the
    input's tile that land in the output's tile; they index the block's
    vregs, slot ``m`` holding ``r_in[m]``. ``swaps``: the exchanges, in
    order, that bring every lane and sublane of the output's tile its
    bit; an exchange is ``(p, q)`` of places ``("L", i)`` (lane bit),
    ``("S", j)`` (sublane bit), ``("R", m)`` (slot). At the end the
    slots hold the input bits of the output bits above the tile whose
    source lay in the input's tile (:func:`_slot_bits`).

    A wanted bit held by a slot comes in by one exchange with it; what
    is left is cycles among lane and sublane places, each done the
    cheaper way by :func:`_swap_cost`: through a slot (``len + 1``
    exchanges of one roll a vreg) or place by place (two rolls a vreg
    for two lanes or two sublanes, two of each for a lane and a
    sublane). Where no bit crosses into the tile the top bit is the
    slot."""
    n = len(pi)
    r_in = tuple(sorted(pi[o] for o in range(TILE_BITS) if pi[o] >= TILE_BITS))
    moved = any(pi[o] != o for o in range(TILE_BITS))
    if not r_in and moved and n > TILE_BITS:
        # no bit crosses into the tile: the top bit, which keeps its
        # place relative to the tile, is the slot lane cycles run through
        r_in = (n - 1,)

    def place_of(bit):
        if bit < LANE_BITS:
            return ("L", bit)
        if bit < TILE_BITS:
            return ("S", bit - LANE_BITS)
        return ("R", r_in.index(bit))

    at = {place_of(b): b for b in list(range(TILE_BITS)) + list(r_in)}
    where = {b: p for p, b in at.items()}
    fixed = [("L", i) for i in range(LANE_BITS)] + [
        ("S", j) for j in range(SUBLANE_BITS)
    ]
    swaps: list[tuple] = []

    def wanted(p):
        return pi[p[1] if p[0] == "L" else LANE_BITS + p[1]]

    def swap(p, q):
        bp, bq = at[p], at[q]
        at[p], at[q] = bq, bp
        where[bp], where[bq] = q, p
        swaps.append((p, q))

    changed = True
    while changed:
        changed = False
        for p in fixed:
            b = wanted(p)
            if at[p] != b and where[b][0] == "R":
                swap(p, where[b])
                changed = True
    for p in fixed:
        if at[p] == wanted(p):
            continue
        cycle = [p]  # cycle[k] wants the bit at cycle[k + 1]
        while where[wanted(cycle[-1])] != p:
            cycle.append(where[wanted(cycle[-1])])
        direct = sum(
            _swap_cost(cycle[k], cycle[k + 1]) for k in range(len(cycle) - 1)
        )
        slot = ("R", 0)
        routed = sum(_swap_cost(slot, q) for q in cycle) + _swap_cost(
            slot, cycle[0]
        )
        if r_in and routed < direct:
            swap(slot, cycle[0])
            for q in reversed(cycle[1:]):
                swap(slot, q)
            swap(slot, cycle[0])
        else:
            for k in range(len(cycle) - 1):
                swap(cycle[k], cycle[k + 1])
    return r_in, tuple(swaps)


def _swap_rolls(p, q) -> tuple[int, int]:
    """``(lane rolls, sublane rolls)`` a vreg of one exchange of places
    ``p`` and ``q``."""
    kinds = sorted((p[0], q[0]))
    return {
        ("L", "R"): (1, 0), ("R", "S"): (0, 1),
        ("L", "L"): (2, 0), ("S", "S"): (0, 2), ("L", "S"): (2, 2),
    }[tuple(kinds)]


def _swap_cost(p, q) -> int:
    """What the planner minimises: on the v5e a lane roll costs about
    ten times a sublane roll (PERF.md, section 6)."""
    lane, sublane = _swap_rolls(p, q)
    return 8 * lane + sublane


def _slot_bits(pi, r_in, swaps) -> tuple[int, ...]:
    """The input bit each slot holds once ``swaps`` ran."""
    at = {("L", i): i for i in range(LANE_BITS)}
    at.update({("S", j): LANE_BITS + j for j in range(SUBLANE_BITS)})
    at.update({("R", m): b for m, b in enumerate(r_in)})
    for p, q in swaps:
        at[p], at[q] = at[q], at[p]
    return tuple(at[("R", m)] for m in range(len(r_in)))


@dataclass(frozen=True)
class PassPlan:
    """How one staged prep runs as the pass. ``pi``: the bit
    permutation; ``r_in`` / ``swaps``: :func:`swap_plan`;
    ``loops``: runs of bits that keep their order on both sides, each
    ``(input low bit, output low bit, width)``, looped over inside a
    block; ``grid``: every other input bit above the tile, ascending,
    one block each value; ``image``: the shape the pass writes."""

    pi: tuple[int, ...]
    r_in: tuple[int, ...]
    swaps: tuple[tuple, ...]
    loops: tuple[tuple[int, int, int], ...]
    grid: tuple[int, ...]
    image: tuple[int, ...]


def _loop_runs(pi, taken, budget):
    """Runs ``(input low bit, output low bit, width)`` of input bits
    above the tile, outside ``taken``, whose output places are
    consecutive in the same order; the longest first (ties: the lowest),
    ``budget`` bits in all."""
    n = len(pi)
    inv = {b: o for o, b in enumerate(pi)}
    runs = []
    b = TILE_BITS
    while b < n:
        if b in taken:
            b += 1
            continue
        width = 1
        while (
            b + width < n
            and b + width not in taken
            and inv[b + width] == inv[b] + width
        ):
            width += 1
        runs.append((b, inv[b], width))
        b += width
    chosen = []
    for lo, olo, width in sorted(runs, key=lambda r: (-r[2], r[0])):
        if budget <= 0:
            break
        width = min(width, budget)  # the low end: longer contiguous reads
        chosen.append((lo, olo, width))
        budget -= width
    return tuple(sorted(chosen))


def pass_plan(n_elems: int, ops: tuple) -> PassPlan | None:
    """The rule, on shapes alone: a staged prep (``ops`` of
    :func:`tnc_tpu.ops.program.tiled_prep_ops` that hold a ``lanemix``)
    of ``n_elems`` elements takes the pass where it holds
    :data:`MIN_PASS_ELEMS` elements or more, every digit of it is a
    power of two and the plan permutes index bits
    (:func:`bit_permutation`), its lane window ``W`` is a power of two
    from 128 to :data:`MAX_WINDOW`, and the image it ends in has at
    least one ``(8, 128)`` tile and a multiple of 8 rows
    (``(M / 128, 2k, 128)`` with ``2k % 8 == 0``). ``None``: the
    one-hot matmul keeps it."""
    if n_elems < MIN_PASS_ELEMS:
        return None
    return _shape_plan(n_elems, ops)


@functools.lru_cache(maxsize=4096)
def _shape_plan(n_elems: int, ops: tuple) -> PassPlan | None:
    """:func:`pass_plan` but for the operand's size."""
    windows = [op[1] for op in ops if op[0] == "lanemix"]
    if len(windows) != 1:
        return None
    w = _log2(windows[0])
    if w is None or not LANE_BITS <= w <= _log2(MAX_WINDOW):
        return None
    if ops[-1][0] != "reshape":
        return None
    image = tuple(ops[-1][1])
    if len(image) < 2 or image[-1] != 128 or image[-2] % 8:
        return None
    n = _log2(n_elems)
    if n is None or n < TILE_BITS:
        return None
    pi = bit_permutation(n_elems, ops)
    if pi is None:
        return None
    return _build_plan(pi, image, BLOCK_BITS)


def _build_plan(pi, image, block_bits: int) -> PassPlan:
    """The plan of a permutation ``pi`` into ``image`` whose blocks hold
    ``2^block_bits`` elements or fewer."""
    n = len(pi)
    r_in, swaps = swap_plan(pi)
    loops = _loop_runs(
        pi, set(r_in), max(0, block_bits - TILE_BITS - len(r_in))
    )
    looped = {lo + t for lo, _, width in loops for t in range(width)}
    grid = tuple(
        b for b in range(TILE_BITS, n) if b not in r_in and b not in looped
    )
    return PassPlan(pi, r_in, swaps, loops, grid, image)


def _axes(bits_desc, kind_of):
    """Runs of consecutive bits (most significant first) of one kind:
    ``[(kind, low bit, width)]`` — the view's axes above the tile."""
    axes: list[list] = []
    for b in bits_desc:
        k = kind_of(b)
        if axes and axes[-1][0] == k and axes[-1][1] == b + 1:
            axes[-1][1] = b
            axes[-1][2] += 1
        else:
            axes.append([k, b, 1])
    return [tuple(a) for a in axes]


def _views(plan: PassPlan):
    """``(in_axes, out_axes)``: the axes of the input's and the output's
    view above their ``(8, 128)`` tile, most significant first, as
    ``(kind, low bit, width)`` with kind ``("R",)`` (a vreg of the
    static set), ``("L", k)`` (loop ``k``) or ``("G",)`` (a grid
    axis)."""
    n = len(plan.pi)
    loop_of = {}
    for k, (lo, olo, width) in enumerate(plan.loops):
        for t in range(width):
            loop_of[lo + t] = (k, t)

    def in_kind(b):
        if b in plan.r_in:
            return ("R",)
        if b in loop_of:
            return ("L", loop_of[b][0])
        return ("G",)

    def out_kind(o):
        src = plan.pi[o]
        if src < TILE_BITS or src in plan.r_in:
            return ("R",)
        if src in loop_of:
            return ("L", loop_of[src][0])
        return ("G",)

    above_in = list(range(n - 1, TILE_BITS - 1, -1))
    return _axes(above_in, in_kind), _axes(above_in, out_kind)


def staged_image(x, plan: PassPlan, interpret: bool = False):
    """The image ``plan.image`` of the streamed operand ``x`` (any
    shape, ``2^n`` f32 elements in row-major order: a carried ``(2,) +
    stored`` value or a :class:`tnc_tpu.ops.split_complex.TiledValue`'s
    array), by the pass. ``interpret``: the caller's, from the device it
    targets (:func:`tnc_tpu.ops.split_complex.interpret_for`)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pi = plan.pi
    in_axes, out_axes = _views(plan)
    rank_in_grid = {b: t for t, b in enumerate(plan.grid)}
    c = len(plan.r_in)
    slot_bits = _slot_bits(pi, plan.r_in, plan.swaps)
    inv = {b: o for o, b in enumerate(pi)}

    def shape_of(axes):
        return tuple(1 << width for _, _, width in axes) + (8, 128)

    def block_of(axes):
        return tuple(
            None if kind[0] == "G" else 1 << width for kind, _, width in axes
        ) + (8, 128)

    def const(like, value):
        """``value`` as a scalar of ``like``'s dtype (lax does not
        promote)."""
        return np.array(value, dtype=like.dtype)

    def field(g, lo, width):
        """Bits ``lo .. lo + width`` of the traced counter ``g``."""
        return lax.bitwise_and(
            lax.shift_right_logical(g, const(g, lo)), const(g, (1 << width) - 1)
        )

    def in_index(g):
        idx = []
        for kind, lo, width in in_axes:
            if kind[0] == "G":
                idx.append(field(g, rank_in_grid[lo], width))
            else:
                idx.append(0)
        return tuple(idx) + (0, 0)

    def out_index(g):
        idx = []
        for kind, lo, width in out_axes:
            if kind[0] != "G":
                idx.append(0)
                continue
            value = None
            t = 0
            while t < width:  # runs of grid ranks that rise with t
                r0 = rank_in_grid[pi[lo + t]]
                run = 1
                while (
                    t + run < width
                    and rank_in_grid[pi[lo + t + run]] == r0 + run
                ):
                    run += 1
                part = lax.shift_left(field(g, r0, run), const(g, t))
                value = part if value is None else lax.add(value, part)
                t += run
            idx.append(value)
        return tuple(idx) + (0, 0)

    # the low ``ubits`` bits of the widest loop are a vector axis of
    # every value (``(2^ubits, 8, 128)``), so that a body holds about
    # VREGS_PER_BODY independent vregs and the rolls' latency is hidden;
    # the rest of the loops' bits are one counter ``e``, the widest
    # loop's own first
    widths = [width for _, _, width in plan.loops]
    wide = max(range(len(widths)), key=lambda k: widths[k], default=None)
    ubits = 0
    if wide is not None:
        ubits = min(widths[wide], max(0, _log2(VREGS_PER_BODY) - c))
    rest = [
        width - ubits if k == wide else width for k, width in enumerate(widths)
    ]
    order = ([wide] if wide is not None else []) + [
        k for k in range(len(widths)) if k != wide
    ]
    offsets = {}
    for k in order:
        offsets[k] = sum(rest[j] for j in order[: order.index(k)])

    def loop_index(k, e):
        """Loop ``k``'s index (a slice of ``2^ubits`` for the widest) at
        counter ``e``."""
        part = field(e, offsets[k], rest[k])
        if k == wide:
            return pl.ds(lax.shift_left(part, const(part, ubits)), 1 << ubits)
        return part

    def ref_index(axes, loops, bit_value):
        """Static index of a vreg in a block: loops' counters for loop
        axes, ``bit_value(bit)`` for the static set's bits."""
        idx = []
        for kind, lo, width in axes:
            if kind[0] == "G":
                continue
            if kind[0] == "L":
                idx.append(loops[kind[1]])
                continue
            idx.append(sum(bit_value(lo + t) << t for t in range(width)))
        return tuple(idx)

    def kernel(x_ref, o_ref):
        # The block's vregs are one value of shape ``(2,) * c + vreg``
        # (axis ``c - 1 - m`` is slot bit ``m``), and every op is a lax
        # primitive: the kernel is traced and lowered again in every
        # process (the compile cache keeps only the compiled program), so
        # its op count is set-up time. A jnp call would trace a jit of
        # its own.
        vreg = ((1 << ubits,) if wide is not None else ()) + (8, 128)
        shape = (2,) * c + vreg
        iota = {
            "L": lax.broadcasted_iota(jnp.int32, (8, 128), 1),
            "S": lax.broadcasted_iota(jnp.int32, (8, 128), 0),
        }

        def roll(x, place, shift):
            axis, size = (-1, 128) if place[0] == "L" else (-2, 8)
            return pltpu.roll(x, shift % size, x.ndim + axis)

        def halves(x, m):
            """The two halves of ``x`` along slot bit ``m``."""
            return (
                lax.slice_in_dim(x, 0, 1, axis=c - 1 - m),
                lax.slice_in_dim(x, 1, 2, axis=c - 1 - m),
            )

        def exchange(x, p, q, mask):
            """Exchange the bits at places ``p`` and ``q``: ``y[i] =
            x[i with the two bits swapped]`` over the block."""
            if q[0] == "R":
                p, q = q, p
            if p[0] == "R":  # a slot and a lane or sublane bit
                m, step = p[1], 1 << q[1]
                x0, x1 = halves(x, m)
                mq = mask(q, x0.shape)
                return lax.concatenate(
                    [
                        lax.select(mq, roll(x1, q, step), x0),
                        lax.select(mq, x1, roll(x0, q, -step)),
                    ],
                    c - 1 - m,
                )
            mp, mq = mask(p, shape), mask(q, shape)
            lo_hi = lax.bitwise_and(mp, lax.bitwise_not(mq))
            hi_lo = lax.bitwise_and(lax.bitwise_not(mp), mq)
            if p[0] == q[0]:  # two lanes or two sublanes
                d = (1 << q[1]) - (1 << p[1])
                up, down = roll(x, p, -d), roll(x, p, d)
            else:  # a lane and a sublane
                up = roll(roll(x, p, 1 << p[1]), q, -(1 << q[1]))
                down = roll(roll(x, p, -(1 << p[1])), q, 1 << q[1])
            return lax.select(lo_hi, up, lax.select(hi_lo, down, x))

        def body(e, carry):
            masks = {}

            def mask(place, to):
                """Where the bit at ``place`` is set, over a value of
                shape ``to``."""
                if place not in masks:
                    bit = lax.bitwise_and(
                        iota[place[0]], np.int32(1 << place[1])
                    )
                    masks[place] = lax.ne(bit, lax.full_like(bit, 0))
                if (place, to) not in masks:
                    masks[place, to] = lax.broadcast_in_dim(
                        masks[place], to, (len(to) - 2, len(to) - 1)
                    )
                return masks[place, to]

            loops = [loop_index(k, e) for k in range(len(widths))]
            x = lax.concatenate(
                [
                    lax.reshape(
                        x_ref[
                            ref_index(
                                in_axes, loops,
                                lambda b, r=r: (r >> plan.r_in.index(b)) & 1,
                            )
                        ],
                        (1,) + vreg,
                    )
                    for r in range(1 << c)
                ],
                0,
            )
            x = lax.reshape(x, shape)
            for p, q in plan.swaps:
                x = exchange(x, p, q, mask)
            x = lax.reshape(x, (1 << c,) + vreg)
            for r in range(1 << c):
                # an output bit above the tile whose source sits in a
                # slot takes that slot's value
                value = {
                    inv[bit]: (r >> m) & 1 for m, bit in enumerate(slot_bits)
                }
                o_ref[ref_index(out_axes, loops, lambda o: value[o])] = (
                    lax.reshape(lax.slice_in_dim(x, r, r + 1), vreg)
                )
            return carry

        trips = 1 << sum(rest)
        if trips == 1:
            body(0, None)
        else:
            lax.fori_loop(0, trips, body, None)

    grid = (1 << len(plan.grid),)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(block_of(in_axes), in_index)],
        out_specs=pl.BlockSpec(block_of(out_axes), out_index),
        out_shape=jax.ShapeDtypeStruct(shape_of(out_axes), x.dtype),
        interpret=interpret,
        name="tnc_staged_pass",
    )(x.reshape(shape_of(in_axes)))
    return out.reshape(plan.image)
