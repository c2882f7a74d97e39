"""Split-complex execution: complex tensors as (real, imag) float pairs.

The TPU's MXU is a real-arithmetic systolic array, and this stack exposes
no complex dtypes at all — so the TPU path represents every tensor as two
float32 arrays and lowers each pairwise contraction to real matmuls. The
lowering is read from the step's shape (:func:`default_step_mode`):

- a step whose contraction is short (``2k <= 128``: all but a few steps
  of a network of extent-2 legs) is bound by memory, and runs as **one**
  real dot with the contraction twice as long — the ``block`` form, the
  streamed operand's planes joined along ``k`` against the small
  operand's 2 x 2 block::

      [ar; ai]^T (2k x m) @ [[br, bi], [-bi, br]] (2k x 2n) = [re, im]

  The large operand is read once and the result written once; nothing
  is added or subtracted outside the dot.
- a longer contraction is bound by the MXU, and runs as **three** real
  matmuls via the Gauss/Karatsuba identity (25% fewer flops than the
  naive four)::

      k1 = (ar + ai) @ br
      k2 = ar @ (bi - br)
      k3 = ai @ (br + bi)
      real = k1 - k3,  imag = k1 + k2

This is the "split real/imag representation" contingency the survey
flagged for TPU complex support (SURVEY.md §7 hard parts), promoted to
the primary device layout. Host-side data stays complex128; the split
happens at the host→device boundary.
"""

from __future__ import annotations

import contextlib
import logging
import math
import os
from dataclasses import dataclass
from typing import Any, Sequence

import numpy as np

from tnc_tpu.ops.program import ContractionProgram, PairStep

logger = logging.getLogger(__name__)

#: kernel modes a single step can execute under. ``chain`` is not a
#: per-step mode — chained steps run ``naive`` arithmetic inside one
#: fused multi-step dispatch (see :class:`KernelPolicy`).
KERNEL_MODES = (
    "naive", "gauss", "block", "fused", "fused_transpose", "strassen",
    "chain", "auto",
)

#: longest joined contraction (``2k``) a step runs in the ``block``
#: form: one pass of the MXU's 128-deep tile, where the fourth multiply
#: rides in padding the three-dot form wastes. Above it a step is bound
#: by the MXU and gauss's three multiplies beat four.
BLOCK_MAX_CONTRACT = 128

#: real-multiply credit of each kernel mode relative to the naive
#: 4-dot complex lowering (the unit every flop count in the stack
#: uses): Gauss runs 3 of the 4 dots, one Strassen level on top of
#: Gauss runs 21 half-size sub-GEMMs against naive's 32 half-units.
#: ``bench.py`` multiplies by these so per-bucket MFU stays comparable
#: across kernel modes (effective-flop crediting).
EFFECTIVE_FLOP_FACTOR = {
    "naive": 1.0,
    "block": 1.0,  # naive arithmetic in one real dot
    "fused": 1.0,  # naive arithmetic, fewer HBM passes
    "fused_transpose": 1.0,  # naive arithmetic, no transpose HBM pass
    "gauss": 0.75,
    "strassen": 21.0 / 32.0,  # gauss × one Strassen level
}

#: dot-precision rungs a step can run under on the bf16 MXU (f32 dots
#: are emulated in bf16 passes): ``highest`` = the 6-pass bf16x6
#: recomposition (closest to true f32 — the backend's ``float32``
#: default), ``high`` = the 3-pass bf16x3 (≈2× dot throughput at
#: ≈2^-21 per-product relative error; the rung
#: ``scripts/precision_parity_smoke.py`` pins numerically — its device
#: pass count has never been A/B'd on a chip).
DOT_PRECISION_MODES = ("highest", "high")

#: documented per-dot relative-error rung of bf16x3 (``high``): the
#: 3-term recomposition drops the mid·mid and lo cross products, so
#: its error floor is ~2^-18 relative to the result magnitude —
#: measured per bucket k-length at ≤5.3e-6 by
#: ``scripts/precision_parity_smoke.py``.
#: :func:`plan_precision_modes` only promotes when the run's parity
#: budget clears this rung with 2× headroom; a slice-subset parity
#: check against the complex128 oracle on the device stays the final
#: gate.
HIGH_PRECISION_STEP_REL = 2.0 ** -18


def complex_mult_forced() -> str | None:
    """The ``TNC_TPU_COMPLEX_MULT`` forcing override, read at *trace*
    time (so compiled executables must be keyed by it, like
    ``backends.lanemix_env``), or ``None`` when the knob is unset or
    ``auto``: the step's shape then decides (:func:`default_step_mode`)
    and the promotion ladder may promote (see :func:`plan_kernels`).
    Set, it pins every step to one mode for A/B runs and disables the
    ladder:

    - ``block``: 1 real dot with the contraction twice as long — the
      streamed operand's planes joined along ``k`` against the other
      operand's 2 x 2 block ``[[br, bi], [-bi, br]]``. The large
      operand is read once, the result written once, nothing is added
      outside the dot; the small operand is expanded to four times its
      size. The arithmetic of ``naive`` (rr-ii, ri+ir), accumulated
      inside the dot. What an unforced step of ``2k <= 128`` runs.
    - ``gauss``: 3 real dots via the Gauss/Karatsuba identity —
      25% fewer MXU flops, but the pre-dot operand sums (ar+ai, bi-br,
      br+bi) and the result combines are extra full-operand HBM passes
      AND mix magnitudes, so rounding error is relative to the *larger*
      mixed intermediate (the classic Karatsuba instability). What an
      unforced step of ``2k > 128`` runs.
    - ``naive``: 4 real dots (rr-ii, ri+ir) — each dot's error is
      relative to its own product magnitude (the half-digit-tighter
      rung of the parity ladder).
    - ``fused``: one Pallas kernel computing both outputs with each
      operand tile loaded once (:mod:`tnc_tpu.ops.pallas_complex`);
      naive-mode arithmetic, ~half the operand HBM traffic. Steps the
      kernel cannot take (non-cfirst orientation, ragged/small shapes)
      fall back to ``naive`` per step.
    - ``strassen``: one Strassen recursion level composed with the
      Gauss identity — 21 half-size real sub-GEMMs vs naive's 32
      half-units (:mod:`tnc_tpu.ops.strassen`) — on steps whose
      matricized shape clears the crossover; others run ``gauss``.
    - ``chain``: consecutive small steps grouped by
      :func:`tnc_tpu.ops.program.chain_groups` execute as ONE fused
      multi-step Pallas dispatch (naive arithmetic); ungrouped steps
      run ``gauss``.
    - ``auto``: the explicit spelling of the unforced default, so NOT
      a forced mode.
    """
    mode = os.environ.get("TNC_TPU_COMPLEX_MULT")
    if mode is None or mode == "auto":
        return None
    return mode


def default_step_mode(step) -> str:
    """The lowering of a step nothing promoted, read from its shape:
    ``block`` where the joined contraction fits one pass of the MXU
    (``2k <=`` :data:`BLOCK_MAX_CONTRACT` — the step is bound by
    memory, and one dot moves the fewest bytes), ``gauss`` above (bound
    by the MXU: three multiplies beat four). The one rule under every
    executor: :func:`plan_kernel_steps`' fall-through and
    :func:`apply_step_split` without a policy."""
    from tnc_tpu.ops.program import step_dims

    _, k, _ = step_dims(step)
    return "block" if 2 * k <= BLOCK_MAX_CONTRACT else "gauss"


def step_prep_form(step, mode: str | None = None) -> str:
    """Where the prep of a step's streamed operand ends under ``mode``
    (as :func:`resolved_step_mode` takes it): ``tiled`` — the image of
    its joined matrix, :func:`_tiled_block_step` — for a ``block`` step
    whose streamed operand is large
    (:func:`tnc_tpu.ops.program.stream_prep_form`: the step's shape, no
    knob), ``matrix`` for every other step and lowering. What
    ``ops.step_prep{form=}`` counts."""
    from tnc_tpu.ops.program import stream_prep_form

    if resolved_step_mode(step, mode) != "block":
        return "matrix"
    return stream_prep_form(step)


def complex_mult_key() -> str:
    """Trace-time *cache-key* form of the env knob: the forced mode, or
    ``auto`` when unset. An unset env lets the step's shape and the
    promotion ladder decide (prelude stem GEMMs → strassen), so it must
    NOT share compiled executables with an explicitly forced mode."""
    return os.environ.get("TNC_TPU_COMPLEX_MULT", "auto")


def dot_precision_forced() -> str | None:
    """The ``TNC_TPU_DOT_PRECISION`` forcing override (``high`` /
    ``highest``), or ``None`` when unset — the dot-precision analogue
    of :func:`complex_mult_forced`, the A/B knob for hardware
    campaigns. ``auto`` explicitly requests the per-step ladder
    (:func:`plan_precision_modes`), so it is NOT a forced mode. Read at
    *trace* time — every compiled-fn cache keys on
    :func:`dot_precision_key`."""
    mode = os.environ.get("TNC_TPU_DOT_PRECISION")
    if mode in (None, "", "auto"):
        return None
    if mode not in DOT_PRECISION_MODES:
        # an A/B knob must fail loudly: a typo ('hi') silently running
        # the highest rung would record mislabeled campaign data
        raise ValueError(
            f"TNC_TPU_DOT_PRECISION={mode!r}: expected one of "
            f"{DOT_PRECISION_MODES} or 'auto'"
        )
    return mode


def dot_precision_key() -> str:
    """Trace-time *cache-key* form of ``TNC_TPU_DOT_PRECISION``: the
    forced rung, or ``auto`` when unset — like
    :func:`complex_mult_key`, forced and auto traces must never share
    a compiled executable."""
    return os.environ.get("TNC_TPU_DOT_PRECISION", "auto")


def auto_step_mode(step) -> str | None:
    """Per-step promotion for executors outside a full
    :class:`KernelPolicy` plan (the hoisted prelude, whose stem GEMMs
    are exactly the Strassen regime): ``strassen`` when the step clears
    the crossover and no forcing override is set; ``None`` defers to
    the forcing override, then :func:`default_step_mode`.

    Eligibility-gated only — unlike the full ladder this does NOT
    consult ``_strassen_saving_s``: the prelude executes inside traced
    functions whose caches key on the env, not on a fitted cost model,
    so a model-dependent decision here would silently serve stale
    traces as calibration evolves. On a device where Strassen loses,
    force ``TNC_TPU_COMPLEX_MULT=gauss`` (the A/B knob) to disable."""
    if complex_mult_forced() is not None:
        return None
    if _strassen_step_eligible(step):
        return "strassen"
    return None


def resolved_step_mode(step, mode: str | None = None) -> str:
    """The arithmetic :func:`apply_step_split` actually runs for a
    requested mode — the env/policy name folded through the per-step
    fallbacks (none → the forcing override, else the step's shape
    decides: :func:`default_step_mode`; ``strassen`` below the
    crossover → gauss; ``chain`` / ``auto`` outside a policy → gauss;
    unknown → gauss). The flop-crediting rule
    (:data:`EFFECTIVE_FLOP_FACTOR`) must be looked up on THIS name,
    never the raw request."""
    if mode is None:
        mode = complex_mult_forced() or default_step_mode(step)
    if mode == "strassen":
        return "strassen" if _strassen_step_eligible(step) else "gauss"
    if mode == "fused_transpose":
        # the kernel's per-step gate falls back to the naive dots
        return (
            "fused_transpose"
            if fused_transpose_ineligible_reason(step) is None
            else "naive"
        )
    if mode in ("naive", "fused", "block"):
        return mode
    return "gauss"


def interpret_for(device=None) -> bool:
    """Should Pallas kernels aimed at ``device`` (default: the first JAX
    device) run in interpret mode? Only a CPU device interprets; every
    accelerator compiles the kernel for real. Resolved once where an
    executor learns its device and passed down as ``interpret=`` — the
    kernels never ask the process what it runs on, so a compile for a
    described chip lowers the chip's kernel, not the interpreter's."""
    if device is None:
        import jax

        device = jax.devices()[0]
    return device.platform == "cpu"


def split_array(array: np.ndarray, dtype: str = "float32") -> tuple[np.ndarray, np.ndarray]:
    """Complex array -> contiguous (real, imag) float pair.

    >>> import numpy as np
    >>> re, im = split_array(np.array([1 + 2j, 3 - 4j]))
    >>> re.tolist(), im.tolist()
    ([1.0, 3.0], [2.0, -4.0])
    >>> np.allclose(combine_array(re, im), [1 + 2j, 3 - 4j])
    True
    """
    array = np.asarray(array)
    return (
        np.ascontiguousarray(array.real, dtype=dtype),
        np.ascontiguousarray(array.imag, dtype=dtype),
    )


def combine_array(re: Any, im: Any) -> np.ndarray:
    return np.asarray(re) + 1j * np.asarray(im)


def _resolve_precision(precision):
    """Map the backend's precision knob to a lax.Precision (device only).

    On TPU, f32 dot_generals are emulated on the bf16 MXU: DEFAULT
    truncates to one bf16 pass (fast, ~2^-11 relative), HIGH runs the
    3-pass bf16x3 recomposition, HIGHEST the 6-pass bf16x6 (closest to
    true f32). The parity ladder 'default' < 'high' < 'float32' trades
    dot throughput against the BASELINE 1e-5 amplitude target; the
    campaign A/Bs pick the fastest level that still passes parity."""
    if precision in (None, "default"):
        return None
    from jax import lax

    if precision == "high":
        return lax.Precision.HIGH
    return lax.Precision.HIGHEST


def _resolve_step_precision(precision, precision_mode):
    """The ``lax.Precision`` one step's dots actually run at: the
    per-step :class:`KernelPolicy` rung when set (``high`` /
    ``highest``), else the ``TNC_TPU_DOT_PRECISION`` forcing override,
    else the backend-level ``precision`` knob — device path only (the
    host oracle's f64 matmuls take no precision)."""
    if not precision_mode:
        precision_mode = dot_precision_forced()
    if not precision_mode:
        return _resolve_precision(precision)
    return _resolve_precision(
        "high" if precision_mode == "high" else "float32"
    )


def gauss_matmul(xp, ar, ai, br, bi):
    """Complex matmul on split 2-D parts with 3 real matmuls (host path;
    device precision is handled by `_resolve_precision` + dot_general)."""
    k1 = xp.matmul(ar + ai, br)
    k2 = xp.matmul(ar, bi - br)
    k3 = xp.matmul(ai, br + bi)
    return k1 - k3, k1 + k2


def _as_kl(xp, part, dot_shape, cfirst):
    """Post-prep operand (shaped ``dot_shape``) → contract-dim-leading
    2-D ``(k, frees)`` matrix, the layout the Strassen/fused kernels
    share with the host oracle's ``as_km``."""
    if cfirst:
        return part.reshape(int(dot_shape[0]), -1)
    k = int(dot_shape[-1])
    flat = part.reshape(-1, k)
    return flat.T if xp is np else xp.swapaxes(flat, 0, 1)


def _strassen_step(xp, ar, ai, br, bi, step, precision):
    """One step through the gauss+strassen kernel: matricize both
    prepped operands to kl layout, fold ``swap``, run 21 half-size
    sub-GEMMs (:mod:`tnc_tpu.ops.strassen`)."""
    from tnc_tpu.ops.strassen import gauss_strassen_dot_kl

    a2r = _as_kl(xp, ar, step.a_dot, step.a_cfirst)
    a2i = _as_kl(xp, ai, step.a_dot, step.a_cfirst)
    b2r = _as_kl(xp, br, step.b_dot, step.b_cfirst)
    b2i = _as_kl(xp, bi, step.b_dot, step.b_cfirst)
    if step.swap:
        fr, fi, sr, si = b2r, b2i, a2r, a2i
    else:
        fr, fi, sr, si = a2r, a2i, b2r, b2i
    re, im = gauss_strassen_dot_kl(xp, fr, fi, sr, si, precision=precision)
    return re.reshape(step.out_store), im.reshape(step.out_store)


def apply_step_split(
    xp, apair, bpair, step, precision=None, mode=None, precision_mode=None,
    interpret: bool = False, carry: bool = False, number: int = 0,
):
    """Split-complex analogue of ``backends.apply_step``: one pairwise
    contraction of (real, imag) pairs. The single source of truth
    shared by every split-mode executor. ``mode`` overrides the global
    env mode for this step — the :class:`KernelPolicy` hook; ``None``
    falls back to the forcing override (:func:`complex_mult_forced`),
    then to the step's shape (:func:`default_step_mode`).
    ``precision_mode`` is the policy's per-step dot-precision rung
    (``high``/``highest``; empty defers to the
    ``TNC_TPU_DOT_PRECISION`` override, then the backend
    ``precision``). ``interpret`` runs the Pallas kernels of the
    ``fused`` / ``fused_transpose`` modes in interpret mode — decided by
    the caller from the device it targets (:func:`interpret_for`), never
    from the process.

    ``carry`` is the walker's (:func:`apply_steps_split`), for a result
    its next reader is a later step of the same walk: a ``block`` step
    then hands its result back as ONE array with the plane axis leading,
    ``(2,) + stored``, so the value stays whole from the dot that made
    it to the dot that reads it; ``"tiled"`` where that reader streams
    it through :func:`_tiled_block_step`, which may then leave it as a
    :class:`TiledValue`. An operand may be in either form whatever
    ``carry`` says; without it a pair goes out.

    Off the host oracle the step's ops are traced under ONE named scope
    that says what was decided for it,
    ``tnc.step.<NNNN>.<size>.<mode>.<form>``
    (:func:`tnc_tpu.obs.op_table.step_scope_name`): ``number``, the
    step's index in the step list of the program being traced (the
    walker's); ``large`` | ``small``
    (:func:`tnc_tpu.ops.program.step_size_class`); the lowering that
    runs after every fallback and where the streamed operand's prep
    ends (:func:`_step_lowering`). Inside it the sub-scopes ``prep``
    (the planned transposes and staged ops of the operands), ``dot``
    (the dot, or gauss's three dots and their sums) and ``out`` (the
    result's way to its stored or carried shape). Names in the ops'
    metadata, at trace time only — the compiled program is the same —
    which the optimized program keeps on every instruction:
    :func:`tnc_tpu.obs.device_op_table` maps a trace's ops back to
    steps by them."""
    if xp is np:
        return _host_step_split(apair, bpair, step, mode)
    from tnc_tpu.obs import op_table
    from tnc_tpu.ops.program import step_size_class

    mode, form = _step_lowering(apair, bpair, step, mode)
    scope = op_table.step_scope_name(number, step_size_class(step), mode, form)
    op_table.note_step(number, scope)
    prec = _resolve_step_precision(precision, precision_mode)
    with op_table.named_scope(scope):
        if mode == "block":
            if form == "matrix":
                return _block_step(apair, bpair, step, prec, carry)
            return _tiled_block_step(apair, bpair, step, prec, carry, interpret)
        return _pair_step(apair, bpair, step, mode, prec, interpret)


class TiledValue:
    """A value carried from a tiled step to the tiled step that streams
    it (:func:`_tiled_block_step`), as the dot wrote it: ``array`` is
    ``(rows, 2n, 128)`` — the image of the ``(2n, rows * 128)`` result
    matrix, plane and the ``n`` new elements on the sublane axis — where
    the stored order of a plane is ``(n, rows, 128)``."""

    __slots__ = ("array", "stored")

    def __init__(self, array, stored):
        self.array = array
        self.stored = tuple(stored)

    @property
    def source(self) -> tuple[int, int]:
        """``(rows, n)``, as :func:`tiled_prep_ops` takes it."""
        rows, n2, _ = self.array.shape
        return int(rows), int(n2) // 2

    def plain(self):
        """The same value as ``(2,) + stored``: one pass over it."""
        import jax.numpy as jnp

        rows, n = self.source
        return jnp.transpose(
            self.array.reshape(rows, 2, n, -1), (1, 2, 0, 3)
        ).reshape((2,) + self.stored)


def _untiled(value):
    return value.plain() if isinstance(value, TiledValue) else value


def _carry_form(reader, slot: int, mode: str | None):
    """How a result is carried to ``reader``, the next step of the walk
    that reads it from ``slot``: ``"tiled"`` where the reader streams it
    through :func:`_tiled_block_step`, else ``True`` (one ``(2,) +
    stored`` array)."""
    from tnc_tpu.ops.program import streamed_side

    streamed = reader.lhs if streamed_side(reader) == "a" else reader.rhs
    if streamed == slot and step_prep_form(reader, mode) == "tiled":
        return "tiled"
    return True


def _as_pair(value):
    """A value of the walker as its (real, imag) pair: a pair as it is,
    a carried ``(2,) + stored`` array cut into its planes."""
    value = _untiled(value)
    return value if isinstance(value, tuple) else (value[0], value[1])


def _plane_meta(value) -> tuple[str, int]:
    """``(dtype, elements of a plane)`` of a walker's value in any of
    its forms: a pair, a carried ``(2,) + stored`` array, a
    :class:`TiledValue`. Shapes only: no op is traced."""
    if isinstance(value, tuple):
        return str(value[0].dtype), int(value[0].size)
    array = value.array if isinstance(value, TiledValue) else value
    return str(array.dtype), int(array.size) // 2


def _tiled_stream_ops(value, step):
    """``(ops, from_image)`` of the streamed operand ``value`` of a
    tiled step: the ops that bring it to the tiled image
    (:func:`tnc_tpu.ops.program.tiled_prep_ops`), starting from the
    image a tiled step left (``from_image``: ``value`` is a
    :class:`TiledValue` and its digits refine) or from ``(2,) +
    stored``."""
    from tnc_tpu.ops.program import operand_prep, streamed_side, tiled_prep_ops

    view, perm, dot, cfirst, ops = operand_prep(step, streamed_side(step))
    if isinstance(value, TiledValue):
        tiled = tiled_prep_ops(
            view, perm, dot, cfirst, ops is not None, value.source
        )
        if tiled is not None:
            return tiled, True
    return tiled_prep_ops(view, perm, dot, cfirst, ops is not None), False


def _stream_pass(value, ops):
    """The plan of the one Pallas pass that runs a staged prep of the
    streamed operand ``value`` (:func:`tnc_tpu.ops.staged_pass.pass_plan`:
    the plan's shape decides, float32 planes only), or ``None`` where
    the one-hot matmul keeps it. Shapes and dtypes alone: no op is
    traced."""
    from tnc_tpu.ops.staged_pass import pass_plan

    dtype, plane = _plane_meta(value)
    return pass_plan(2 * plane, ops) if dtype == "float32" else None


def _step_lowering(a, b, step, mode) -> tuple[str, str]:
    """``(mode, form)`` of one step on the device, decided before any of
    its ops is traced (shapes and dtypes alone) and counted
    (:func:`_note_step_lowering`): the lowering that runs after every
    fallback — ``block``, ``gauss``, ``naive``, ``strassen``,
    ``fused``, ``fused_transpose``; a fused kernel's routing away is
    counted with its reason here — and where the prep of the streamed
    operand ends: ``tiled`` (the image of its joined matrix,
    :func:`_tiled_block_step`), ``staged`` (tiled through a ``lanemix``
    plan) or ``matrix`` (every other step and lowering). A staged prep
    runs as one Pallas pass or through the one-hot matmul, as its shape
    says (:func:`_stream_pass`), counted ``ops.lanemix{impl=pass |
    matmul}``."""
    from tnc_tpu.ops.program import step_dims, streamed_side

    resolved = mode or complex_mult_forced() or default_step_mode(step)
    if resolved == "block":
        form, lanemix = "matrix", None
        if step_prep_form(step, resolved) == "tiled":
            streamed = a if streamed_side(step) == "a" else b
            ops, _ = _tiled_stream_ops(streamed, step)
            staged = any(op[0] == "lanemix" for op in ops)
            form = "staged" if staged else "tiled"
            if staged:
                lanemix = "matmul" if _stream_pass(streamed, ops) is None else "pass"
        _note_step_lowering(
            "block", "matrix" if form == "matrix" else "tiled", lanemix
        )
        return "block", form
    if mode == "fused_transpose":
        reason = fused_transpose_ineligible_reason(
            step
        ) or fused_transpose_runtime_ineligible_reason(a, b, step)
        if reason is None:
            _note_step_lowering("fused_transpose")
            return "fused_transpose", "matrix"
        m, k, n = step_dims(step)
        _note_fused_transpose_fallback(reason, k, m, n)
        mode = "naive"  # the prep + naive path: same arithmetic
    if mode is None:
        mode = resolved
    if mode == "strassen" and not _strassen_step_eligible(step):
        mode = "gauss"  # forced-strassen steps below the crossover
    if mode == "fused":
        fallback = _fused_ineligible_reason(step, _plane_meta(a)[0])
        if fallback is not None:
            _note_fused_fallback(*fallback)
            mode = "naive"  # routed away by eligibility: same arithmetic
    if mode not in ("strassen", "fused", "naive"):
        mode = "gauss"
    _note_step_lowering(mode)
    return mode, "matrix"


def _host_step_split(apair, bpair, step, mode):
    """One step on the host oracle (numpy, f64 planes)."""
    from tnc_tpu.ops.backends import _prep_operand

    apair, bpair = _as_pair(apair), _as_pair(bpair)
    ar = _prep_operand(
        np, apair[0], step.a_view, step.a_perm, step.a_dot, step.a_ops
    )
    ai = _prep_operand(
        np, apair[1], step.a_view, step.a_perm, step.a_dot, step.a_ops
    )
    br = _prep_operand(
        np, bpair[0], step.b_view, step.b_perm, step.b_dot, step.b_ops
    )
    bi = _prep_operand(
        np, bpair[1], step.b_view, step.b_perm, step.b_dot, step.b_ops
    )
    if mode is None:
        mode = complex_mult_forced() or default_step_mode(step)
    if mode == "strassen" and _strassen_step_eligible(step):
        return _strassen_step(np, ar, ai, br, bi, step, None)

    def as_km(part, mat, cfirst):
        return part.reshape(mat) if cfirst else part.reshape(mat[::-1]).T

    ar = as_km(ar, step.a_mat, step.a_cfirst)
    ai = as_km(ai, step.a_mat, step.a_cfirst)
    br = as_km(br, step.b_mat, step.b_cfirst)
    bi = as_km(bi, step.b_mat, step.b_cfirst)
    if step.swap:
        ar, ai, br, bi = br.T, bi.T, ar, ai
    else:
        ar, ai = ar.T, ai.T
    if mode in ("naive", "block", "fused", "fused_transpose"):
        # the block form and the fused kernels run naive arithmetic
        # on host oracles
        re = ar @ br - ai @ bi
        im = ar @ bi + ai @ br
    else:
        re, im = gauss_matmul(np, ar, ai, br, bi)
    return re.reshape(step.out_store), im.reshape(step.out_store)


def _pair_step(a, b, step, mode, prec, interpret):
    """One step of a lowering that takes and hands back (real, imag)
    pairs (device path): ``gauss``, ``naive``, ``strassen`` and the two
    fused kernels, as :func:`_step_lowering` decided."""
    import jax.numpy as jnp
    from jax import lax

    from tnc_tpu.obs.op_table import named_scope
    from tnc_tpu.ops.backends import _prep_operand

    with named_scope("prep"):
        apair, bpair = _as_pair(a), _as_pair(b)
    if mode == "fused_transpose":
        # the fused transpose-dot consumes the RAW stored views: the
        # macro transpose _prep_operand would materialize is exactly the
        # pass it deletes
        with named_scope("dot"):
            return _fused_transpose_step(apair, bpair, step, prec, interpret)
    with named_scope("prep"):
        ar = _prep_operand(
            jnp, apair[0], step.a_view, step.a_perm, step.a_dot, step.a_ops
        )
        ai = _prep_operand(
            jnp, apair[1], step.a_view, step.a_perm, step.a_dot, step.a_ops
        )
        br = _prep_operand(
            jnp, bpair[0], step.b_view, step.b_perm, step.b_dot, step.b_ops
        )
        bi = _prep_operand(
            jnp, bpair[1], step.b_view, step.b_perm, step.b_dot, step.b_ops
        )
    if mode == "strassen":
        with named_scope("dot"):
            return _strassen_step(jnp, ar, ai, br, bi, step, prec)
    if mode == "fused":
        with named_scope("dot"):
            return _fused_step(ar, ai, br, bi, step, prec, interpret)
    ca = (0,) if step.a_cfirst else (len(step.a_dot) - 1,)
    cb = (0,) if step.b_cfirst else (len(step.b_dot) - 1,)

    def dot(x, y):
        if step.swap:
            return lax.dot_general(y, x, ((cb, ca), ((), ())), precision=prec)
        return lax.dot_general(x, y, ((ca, cb), ((), ())), precision=prec)

    def stored(part):
        with named_scope("out"):
            return part.reshape(step.out_store)

    if mode == "naive":
        with named_scope("dot"):
            re = dot(ar, br) - dot(ai, bi)
            im = dot(ar, bi) + dot(ai, br)
        return stored(re), stored(im)
    with named_scope("dot"):
        k1 = dot(ar + ai, br)
        k2 = dot(ar, bi - br)
        k3 = dot(ai, br + bi)
        re = k1 - k3
    re = stored(re)
    with named_scope("dot"):
        im = k1 + k2
    return re, stored(im)


def _block_step(a, b, step, precision, carry):
    """One step as ONE real dot (device path), stored operands in: each
    a (real, imag) pair or a carried ``(2,) + stored`` array; the result
    is carried if ``carry``, else a pair.

    The operand with the larger free extent is streamed: its planes are
    joined along the contracted axis (``k -> 2k``) — carried and
    contract-dim-leading, that is a reshape of its two major axes. The
    other is expanded to its 2 x 2 block ``[[er, ei], [-ei, er]]`` along
    (contracted axis, a new free axis of 2 that leads its free dims), so
    with ``s = sr + i si`` the dot's two halves along the new axis are
    ``sr·er - si·ei`` and ``sr·ei + si·er``. Where the expanded operand
    comes first in the dot (under ``swap`` as the plan has it: the larger
    free run supplies the minor dims) that axis is the major-most of the
    result: the result IS the carried value and the halves are never cut
    apart.

    A result that has to leave as a pair is written as one: where it is
    larger than the streamed operand, each half by a dot of its own
    (the streamed operand read twice, no pass over the result to cut
    it); else the one dot, and the halves cut."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from tnc_tpu.obs.op_table import named_scope
    from tnc_tpu.ops.backends import _prep_operand
    from tnc_tpu.ops.program import step_dims, streamed_side

    def prep(value, view, perm, dot_shape, ops):
        def one(plane):
            return _prep_operand(jnp, plane, view, perm, dot_shape, ops)

        if isinstance(value, tuple):
            return one(value[0]), one(value[1])
        return jax.vmap(one)(value)  # the plane axis rides in front

    with named_scope("prep"):
        a = prep(_untiled(a), step.a_view, step.a_perm, step.a_dot, step.a_ops)
        b = prep(_untiled(b), step.b_view, step.b_perm, step.b_dot, step.b_ops)
    m, k, n = step_dims(step)
    # ties expand the operand the dot takes first: the plane axis leads
    expand_a = streamed_side(step) == "b"
    sides = (
        (a, step.a_cfirst, len(step.a_dot)),
        (b, step.b_cfirst, len(step.b_dot)),
    )
    (e, e_cfirst, rank_e), (s, s_cfirst, rank_s) = (
        sides if expand_a else sides[::-1]
    )
    ks = 0 if s_cfirst else rank_s - 1
    ke = 0 if e_cfirst else rank_e - 1
    with named_scope("prep"):
        if s_cfirst and not isinstance(s, tuple):
            joined = s.reshape((2 * s.shape[1],) + s.shape[2:])
        else:
            joined = jnp.concatenate(_as_pair(s), axis=ks)
        er, ei = _as_pair(e)
        halves = [
            jnp.concatenate([er, -ei], axis=ke),
            jnp.concatenate([ei, er], axis=ke),
        ]
    expanded_first = expand_a != step.swap

    def dot(expanded, ke):
        with named_scope("dot"):
            if expanded_first:
                return lax.dot_general(
                    expanded, joined, (((ke,), (ks,)), ((), ())),
                    precision=precision,
                )
            return lax.dot_general(
                joined, expanded, (((ks,), (ke,)), ((), ())),
                precision=precision,
            )

    def stored(out):
        with named_scope("out"):
            return out.reshape(step.out_store)

    if not carry and min(m, n) > k:  # the result outweighs the streamed
        return tuple(stored(dot(h, ke)) for h in halves)
    with named_scope("prep"):
        block = jnp.stack(halves, axis=1 if e_cfirst else 0)
    out = dot(block, ke if e_cfirst else ke + 1)
    with named_scope("out"):
        if not expanded_first:
            out = jnp.moveaxis(out, rank_s - 1, 0)
        out = out.reshape((2,) + tuple(step.out_store))
        return out if carry else _as_pair(out)


def _tiled_block_step(a, b, step, precision, carry, interpret=False):
    """:func:`_block_step` for a step whose streamed operand is large
    (:func:`tnc_tpu.ops.program.stream_prep_form`): the operand's prep
    ends in the **tiled image** of its joined matrix, ``(M / 128, 2k,
    128)`` (:func:`tnc_tpu.ops.program.tiled_prep_ops`), and the one
    real dot contracts axis 1 of it against the other operand's 2 x 2
    block ``(2k, 2n)``: the transpose writes what the dot reads, and
    the chip runs no re-tiling pass between them. The same dot, the same
    k-order and the same products as the matrix form.

    ``carry == "tiled"`` is the walker's word that the next reader
    streams this result through this function: the result then stays as
    the dot wrote it, a :class:`TiledValue`, and the reader's transpose
    starts from it. Every other result leaves in stored order, as from
    :func:`_block_step`.

    A staged prep whose shape the pass takes (:func:`_stream_pass`) is
    ONE Pallas kernel from the stored buffer to the image
    (:func:`tnc_tpu.ops.staged_pass.staged_image`; ``interpret`` as
    :func:`apply_step_split` has it); every other prep runs its ops."""
    import jax.numpy as jnp

    from tnc_tpu.obs.op_table import named_scope
    from tnc_tpu.ops.backends import _prep_operand
    from tnc_tpu.ops.program import operand_prep, step_dims, streamed_side
    from tnc_tpu.ops.staged_pass import staged_image

    stream_a = streamed_side(step) == "a"
    s, e = (a, b) if stream_a else (b, a)
    e_view, e_perm, e_dot, e_cfirst, e_ops = operand_prep(
        step, "b" if stream_a else "a"
    )
    ops, from_image = _tiled_stream_ops(s, step)
    plan = _stream_pass(s, ops)
    ke = 0 if e_cfirst else len(e_dot) - 1
    m, k, n = step_dims(step)
    n = min(m, n)
    # the result's stored order: the legs of the operand the dot takes
    # first, then the other's
    stored = "nrl" if stream_a == step.swap else "rln"
    halved = not carry and n > k  # as _block_step: a dot a half

    with named_scope("prep"):
        if from_image:
            s = s.array
        else:
            s = _untiled(s)
            s = jnp.stack(s) if isinstance(s, tuple) else s
        if plan is None:
            image = _prep_operand(jnp, s, None, None, ops[-1][1], ops)
        else:
            image = staged_image(s, plan, interpret)

        # the other operand's 2 x 2 block, built as it lies and flattened
        # once: (2k, 2n), the halves of the result side by side
        er, ei = (
            _prep_operand(jnp, part, e_view, e_perm, e_dot, e_ops)
            for part in _as_pair(e)
        )
        block = _as_kl(
            jnp,
            jnp.stack(
                [
                    jnp.concatenate([er, -ei], axis=ke),
                    jnp.concatenate([ei, er], axis=ke),
                ],
                axis=1 if e_cfirst else 0,
            ),
            (2 * k, 2 * n) if e_cfirst else (2 * n, 2 * k),
            e_cfirst,
        )
        if halved:
            halves = (block[:, :n], block[:, n:])
        elif stored == "rln":  # the plane leads what is stored: its own axis
            block = block.reshape(2 * k, 2, n)

    def dot(spec, small):
        with named_scope("dot"):
            return jnp.einsum(spec, small, image, precision=precision)

    if halved:
        out = []
        for half in halves:
            part = dot(f"kn,rkl->{stored}", half)
            with named_scope("out"):
                out.append(part.reshape(step.out_store))
        return tuple(out)
    if stored == "rln":
        out = dot("kpn,rkl->prln", block)
    elif carry == "tiled":
        return TiledValue(dot("kq,rkl->rql", block), step.out_store)
    else:
        out = dot("kq,rkl->qrl", block)
    with named_scope("out"):
        out = out.reshape((2,) + tuple(step.out_store))
        return out if carry else _as_pair(out)


def _note_step_lowering(
    mode: str, prep: str = "matrix", lanemix: str | None = None
) -> None:
    """Count the lowering one step was traced under
    (``ops.step_lowering{mode=...}``: once a step a trace, the arithmetic
    that runs after every fallback) — what a record quotes to say how
    far a program ran in the ``block`` form — and, beside it, where the
    prep of its streamed operand ended (``ops.step_prep{form=tiled |
    matrix}``: :func:`_tiled_block_step`, or every other path) and, for
    a staged prep, how its lane permutation runs (``ops.lanemix{impl=pass
    | matmul}``: :func:`_stream_pass`)."""
    from tnc_tpu import obs

    obs.counter_add("ops.step_lowering", mode=mode)
    obs.counter_add("ops.step_prep", form=prep)
    if lanemix is not None:
        obs.counter_add("ops.lanemix", impl=lanemix)


def _strassen_step_eligible(step) -> bool:
    from tnc_tpu.ops.program import step_dims
    from tnc_tpu.ops.strassen import strassen_eligible

    m, k, n = step_dims(step)
    return strassen_eligible(m, k, n)


_FUSED_FALLBACK_WARNED: set[str] = set()


def _note_fused_fallback(reason: str, k: int, m: int, n: int, detail=""):
    """Count a per-step fused-kernel fallback with its reason (the
    ``ops.fused_fallback`` counter bench records pick up) and warn —
    once per reason per process; repeats go to debug so a small-step
    program doesn't spam a warning per step."""
    from tnc_tpu import obs

    obs.counter_add("ops.fused_fallback", reason=reason)
    msg = (
        "fused complex kernel fell back to naive dots for step "
        f"(K={k}, M={m}, N={n}): {reason}{': ' + detail if detail else ''}"
    )
    if reason in _FUSED_FALLBACK_WARNED:
        logger.debug(msg)
    else:
        _FUSED_FALLBACK_WARNED.add(reason)
        logger.warning(msg)


def _fused_ineligible_reason(step, dtype: str) -> tuple | None:
    """Why the fused Pallas kernel cannot take one step, as the
    arguments of :func:`_note_fused_fallback` — ``(reason, k, m, n,
    detail)``: layout (both operands must be contract-dim-leading) vs
    dtype vs tile/flop floor — or ``None`` where it can. Shapes and the
    planes' dtype alone: routing is planning, decided before any op of
    the step is traced (:func:`_step_lowering`)."""
    k = int(step.a_dot[0]) if step.a_cfirst else int(step.a_dot[-1])
    m = int(np.prod(step.a_dot, dtype=np.int64)) // max(k, 1)
    n = int(np.prod(step.b_dot, dtype=np.int64)) // max(k, 1)
    if step.swap:
        m, n = n, m
    if not (step.a_cfirst and step.b_cfirst):
        return ("layout", k, m, n, "")
    from tnc_tpu.ops.pallas_complex import ineligible_reason

    if dtype != "float32":
        return ("dtype", k, m, n, dtype)
    reason = ineligible_reason(k, m, n)
    return None if reason is None else (reason, k, m, n, "")


def _fused_step(ar, ai, br, bi, step, precision, interpret=False):
    """One step through the fused Pallas kernel
    (:func:`_fused_ineligible_reason` found no reason against it). A
    kernel that was routed here and cannot trace or compile fails the
    run."""
    from tnc_tpu.ops.pallas_complex import fused_complex_dot_kl

    k = int(step.a_dot[0])
    a2r, a2i = ar.reshape(k, -1), ai.reshape(k, -1)
    b2r, b2i = br.reshape(k, -1), bi.reshape(k, -1)
    if step.swap:
        re, im = fused_complex_dot_kl(
            b2r, b2i, a2r, a2i, interpret=interpret, precision=precision
        )
    else:
        re, im = fused_complex_dot_kl(
            a2r, a2i, b2r, b2i, interpret=interpret, precision=precision
        )
    return re.reshape(step.out_store), im.reshape(step.out_store)


# -- fused transpose-matmul step glue -----------------------------------


_FUSED_TRANSPOSE_WARNED: set[str] = set()


def _note_fused_transpose_fallback(reason: str, k: int, m: int, n: int, detail=""):
    """Count a per-step fused-transpose fallback with its reason (the
    ``ops.fused_transpose_fallback`` counter bench's
    ``kernel_counters`` block picks up) and warn — once per reason per
    process, mirroring :func:`_note_fused_fallback`."""
    from tnc_tpu import obs

    obs.counter_add("ops.fused_transpose_fallback", reason=reason)
    msg = (
        "fused transpose-dot kernel fell back to prep+naive dots for "
        f"step (K={k}, M={m}, N={n}): {reason}"
        f"{': ' + detail if detail else ''}"
    )
    if reason in _FUSED_TRANSPOSE_WARNED:
        logger.debug(msg)
    else:
        _FUSED_TRANSPOSE_WARNED.add(reason)
        logger.warning(msg)


def _fused_transpose_layouts(step):
    """``(first, second)`` :class:`~tnc_tpu.ops.pallas_complex.
    OperandLayout` pair for one step with ``swap`` folded out (the
    first operand supplies the output rows), or ``None`` per side when
    the operand's layout cannot be described (staged-prep operands are
    rejected by the caller — their reshape/lanemix plans are baked for
    the flat buffer)."""
    from tnc_tpu.ops.pallas_complex import operand_layout

    a = operand_layout(step.a_view, step.a_perm, step.a_dot, step.a_cfirst)
    b = operand_layout(step.b_view, step.b_perm, step.b_dot, step.b_cfirst)
    return (b, a) if step.swap else (a, b)


def fused_transpose_ineligible_reason(step) -> str | None:
    """Why the fused transpose-dot cannot take one step — ``None``
    when it can (the static half of the gate; dtype and batch checks
    need live buffers and happen in :func:`_step_lowering`).
    ``staged_prep`` rejects operands carrying a staged op plan: their
    minor-dim-safe reshape/lanemix sequence is the materialization the
    kernel would otherwise have to replicate per tile."""
    from tnc_tpu.ops.pallas_complex import transpose_dot_ineligible_reason
    from tnc_tpu.ops.program import step_dims

    if step.a_ops is not None or step.b_ops is not None:
        return "staged_prep"
    m, k, n = step_dims(step)
    first, second = _fused_transpose_layouts(step)
    return transpose_dot_ineligible_reason(first, second, k, m, n)


def fused_transpose_step_eligible(step) -> bool:
    """Can the fused transpose-dot take this step (the static gate)?"""
    return fused_transpose_ineligible_reason(step) is None


def fused_transpose_runtime_ineligible_reason(apair, bpair, step) -> str | None:
    """The *runtime* half of the fused-transpose gate — conditions the
    static :func:`fused_transpose_ineligible_reason` cannot see because
    they need live buffers: non-f32 parts (``dtype``) and buffers
    carrying an extra leading batch axis (``batch`` — serving rebind
    threading cannot stream through the static block geometry). The
    ONE predicate shared by the kernel route
    (:func:`_step_lowering`) and the span accounting
    (``backends.run_steps_timed``), so what the spans credit and what
    the kernel actually does can never diverge."""
    (a_dtype, a_size), (b_dtype, b_size) = _plane_meta(apair), _plane_meta(bpair)
    if a_dtype != "float32" or b_dtype != "float32":
        return "dtype"
    if a_size != int(np.prod(step.a_view, dtype=np.int64)) or b_size != int(
        np.prod(step.b_view, dtype=np.int64)
    ):
        return "batch"
    return None


def _fused_transpose_step(apair, bpair, step, precision, interpret=False):
    """One step through the fused transpose-dot Pallas kernel
    (:func:`tnc_tpu.ops.pallas_complex.fused_transpose_dot_kl`), the
    gate passed (:func:`_step_lowering`: every routed-away step is
    counted there, ``ops.fused_transpose_fallback{reason=...}``). Takes
    the RAW stored (real, imag) pairs — the whole point is that the
    macro transpose is applied in the kernel's index maps, not
    materialized through HBM. Like :func:`_fused_step`, a kernel that
    cannot trace or compile fails the run."""
    ar, ai = apair
    br, bi = bpair
    from tnc_tpu.ops.pallas_complex import fused_transpose_dot_kl

    first_lay, second_lay = _fused_transpose_layouts(step)
    a2 = (ar.reshape(step.a_view), ai.reshape(step.a_view))
    b2 = (br.reshape(step.b_view), bi.reshape(step.b_view))
    first, second = (b2, a2) if step.swap else (a2, b2)
    re, im = fused_transpose_dot_kl(
        first[0], first[1], second[0], second[1],
        first_lay, second_lay,
        interpret=interpret, precision=precision,
    )
    return re.reshape(step.out_store), im.reshape(step.out_store)


# -- kernel promotion ladder --------------------------------------------


@dataclass(frozen=True)
class KernelPolicy:
    """Per-step kernel choice for one compiled program.

    ``modes[i]`` is the lowering of step ``i`` (``naive`` / ``gauss`` / ``block`` /
    ``fused`` / ``fused_transpose`` / ``strassen``); ``chains`` are
    ``(start, end)`` step spans that execute as ONE fused multi-step
    Pallas dispatch
    (:func:`tnc_tpu.ops.pallas_complex.fused_chain_kl`). Chained steps
    carry mode ``naive`` — the chain kernel's arithmetic — so the host
    oracle and the per-step device fallback compute the identical
    sequence. ``precision_modes[i]`` is step ``i``'s dot-precision
    rung (``highest`` / ``high`` = bf16x3; empty string defers to the
    ``TNC_TPU_DOT_PRECISION`` override, then the backend precision);
    the empty tuple means no step carries a rung. A policy is part of
    the jit cache key (:func:`tnc_tpu.ops.backends.jit_program`): two
    policies over the same program — including two that differ ONLY in
    precision rungs — are different executables.
    """

    modes: tuple[str, ...]
    chains: tuple[tuple[int, int], ...] = ()
    precision_modes: tuple[str, ...] = ()

    def signature(self) -> tuple:
        return (self.modes, self.chains, self.precision_modes)

    def precision_mode(self, i: int) -> str:
        """Step ``i``'s dot-precision rung ('' = defer)."""
        return self.precision_modes[i] if self.precision_modes else ""

    def chained_steps(self) -> set[int]:
        return {i for s, e in self.chains for i in range(s, e)}

    def dispatch_count(self) -> int:
        """Device dispatches this policy issues: one per unchained step,
        one per chain."""
        return len(self.modes) - len(self.chained_steps()) + len(self.chains)


def _strassen_saving_s(cost_model, m: int, k: int, n: int) -> float:
    """Predicted seconds one Strassen level saves over gauss on an
    eligible step (negative = loses): the saved multiplies (0.75 →
    21/32 of naive) against the 15 extra quadrant-sized elementwise
    passes per real GEMM (bandwidth). With no fitted model the margin
    is ``+inf`` — eligibility alone decides, the pre-calibration
    behavior."""
    if cost_model is None:
        return float("inf")
    from tnc_tpu.ops.strassen import GAUSS_STRASSEN_FLOP_FACTOR

    naive_real_flops = 8.0 * m * k * n
    saved_s = (
        0.75 - GAUSS_STRASSEN_FLOP_FACTOR
    ) * naive_real_flops / cost_model.flops_per_s
    if not cost_model.bytes_per_s:
        return saved_s
    # ~15 add/sub passes over (m/2, k/2)+(k/2, n/2) quadrants, 3 Gauss
    # products, f32 in + out
    quad_bytes = 4.0 * ((m * k + k * n) / 4.0) * 2.0
    extra_s = 3.0 * 15.0 * quad_bytes / cost_model.bytes_per_s
    return saved_s - extra_s


def _fused_transpose_saving_s(cost_model, step) -> float:
    """Predicted seconds the fused transpose-dot saves over the
    default prep+gauss path on one eligible step (negative = loses):
    the deleted materialized-transpose HBM pass (read + write of every
    permuted operand's (real, imag) pair — :func:`tnc_tpu.ops.program.
    step_prep_elems`) against the naive-vs-gauss flop difference (the
    kernel runs 4 dots where gauss runs 3). Unlike Strassen, a missing
    model means NO promotion (``-inf``): the rung's entire case is
    bandwidth, so without a fitted bandwidth term there is no evidence
    it pays — the ``TNC_TPU_COMPLEX_MULT=fused_transpose`` override is
    the A/B path."""
    if cost_model is None or not cost_model.bytes_per_s:
        return float("-inf")
    from tnc_tpu.ops.program import step_flops, step_prep_elems

    prep = step_prep_elems(step)
    if prep <= 0.0:
        return float("-inf")  # no transpose pass to save
    # f32 split pairs: 8 bytes per complex element, the device width
    saved_s = prep * 8.0 / cost_model.bytes_per_s
    # naive 8 vs gauss 6 real-multiply units per k*m*n; the fitted
    # flops_per_s is per k*m*n unit
    extra_s = 2.0 * step_flops(step) / cost_model.flops_per_s
    return saved_s - extra_s


def plan_precision_modes(
    steps,
    cost_model=None,
    force: str | None = None,
    parity_budget: float = 1e-5,
) -> tuple[str, ...]:
    """Per-step dot-precision rungs for :func:`plan_kernel_steps`.

    ``force`` (default: the ``TNC_TPU_DOT_PRECISION`` override via
    :func:`dot_precision_forced`) pins every step for A/B runs.
    Unforced, the ladder promotes a step to ``high`` (bf16x3, ≈2× dot
    throughput) only when ALL of:

    - a fitted cost model with a bandwidth term exists and predicts the
      step *compute*-dominated (flop time > byte time) — elsewhere the
      dots aren't the bottleneck and the rung buys nothing;
    - the step is in the ``stem`` bucket — the big square-ish GEMMs
      whose products dominate the amplitude, where
      ``scripts/precision_parity_smoke.py`` pins the bf16x3 rung's
      measured relative error;
    - the ``parity_budget`` (the run's amplitude-parity target, 1e-5
      by default) clears the documented bf16x3 rung
      (:data:`HIGH_PRECISION_STEP_REL`, ~3.8e-6) with 2× headroom —
      a tight-budget run never trades parity for speed.

    Returns ``()`` (no rungs) when nothing promotes, so unpromoted
    policies keep their pre-ladder signatures.
    """
    steps = tuple(steps)
    if force is None:
        force = dot_precision_forced()
    if force is not None:
        return (force,) * len(steps)
    if cost_model is None or not cost_model.bytes_per_s:
        return ()
    if parity_budget < 2.0 * HIGH_PRECISION_STEP_REL:
        return ()
    from tnc_tpu.ops.program import step_elems, step_flops

    out = []
    for st in steps:
        promote = False
        if step_bucket(st) == "stem":
            flop_s = step_flops(st) / cost_model.flops_per_s
            elems_in, elems_out = step_elems(st)
            byte_s = (elems_in + elems_out) * 8.0 / cost_model.bytes_per_s
            promote = flop_s > byte_s
        out.append("high" if promote else "")
    if not any(out):
        return ()
    return tuple(out)


def plan_kernels(
    program: ContractionProgram,
    cost_model=None,
    force: str | None = None,
    chain_max_flops: float | None = None,
) -> KernelPolicy:
    """Build the kernel promotion ladder for one program — the
    per-step decision that replaced the global env mode. Thin wrapper
    over :func:`plan_kernel_steps` (the chunked executor plans per
    chunk-subsequence with the same rules).

    ``force`` (default: the ``TNC_TPU_COMPLEX_MULT`` override via
    :func:`complex_mult_forced`) pins the decision for A/B runs:
    ``naive``/``gauss``/``block``/``fused``/``fused_transpose`` uniformly
    (the fused rungs fall back per step at trace time, counted);
    ``strassen`` promotes every step over the crossover (others run
    gauss); ``chain`` fuses every groupable run (others run gauss) —
    the only way to a chain: the unforced ladder plans none, because
    the chain kernel has never compiled for a TPU.
    The per-step dot-precision rung is planned alongside
    (:func:`plan_precision_modes` — ``TNC_TPU_DOT_PRECISION`` forces
    it independently of the mode override). Unforced, the ladder is
    cost-model-driven (``cost_model``: a
    :class:`tnc_tpu.obs.calibrate.CalibratedCostModel` or None):

    - transpose-carrying steps the fused transpose-dot can stream
      where the deleted HBM transpose pass beats the extra naive dot
      (:func:`_fused_transpose_saving_s` — needs a fitted bandwidth
      term) → **fused_transpose**;
    - steps whose matricized shape clears the Strassen crossover
      (square-ish, ≥2^11 per dim) where the multiply saving beats the
      extra passes → **strassen** (when both rungs pay, the larger
      predicted saving wins);
    - everything else by its shape (:func:`default_step_mode`):
      **block** where ``2k <= 128``, **gauss** above;
    - stem-bucket compute-dominated steps additionally promote their
      dots to the bf16x3 ``high`` rung under the parity budget.
    """
    return plan_kernel_steps(
        program.steps, cost_model, force, chain_max_flops
    )


def plan_kernel_steps(
    steps,
    cost_model=None,
    force: str | None = None,
    chain_max_flops: float | None = None,
    precision_force: str | None = None,
    parity_budget: float = 1e-5,
) -> KernelPolicy:
    """:func:`plan_kernels` over a bare step sequence — chain spans and
    modes are indexed relative to ``steps[0]``."""
    from tnc_tpu.ops.program import chain_groups, step_dims
    from tnc_tpu.ops.strassen import strassen_eligible

    steps = tuple(steps)
    n = len(steps)
    if force is None:
        force = complex_mult_forced()
    pmodes = plan_precision_modes(
        steps, cost_model, precision_force, parity_budget
    )
    if force in ("naive", "gauss", "block", "fused", "fused_transpose"):
        return KernelPolicy((force,) * n, (), pmodes)
    if force == "strassen":
        modes = tuple(
            "strassen" if _strassen_step_eligible(st) else "gauss"
            for st in steps
        )
        if pmodes and dot_precision_forced() is None and precision_force is None:
            # see the auto branch below: no auto bf16x3 on strassen
            pmodes = tuple(
                "" if modes[i] == "strassen" else p
                for i, p in enumerate(pmodes)
            )
            if not any(pmodes):
                pmodes = ()
        return KernelPolicy(modes, (), pmodes)

    # The chain kernel has never compiled for a TPU: Mosaic refuses
    # _chain_compute's in-kernel regroup of the carried value
    # ("infer-vector-layout: unsupported shape cast", asked of the v5e
    # compiler off-chip), and that refusal surfaces when the enclosing
    # jit compiles, past any trace-time handling. So the unforced
    # policy plans no chains, with or without a fitted model; only
    # force="chain" does (the interpret-mode tests).
    chains = (
        chain_groups(steps, max_flops=chain_max_flops)
        if force == "chain"
        else ()
    )
    chained = {i for s, e in chains for i in range(s, e)}
    modes = []
    for i, st in enumerate(steps):
        if i in chained:
            modes.append("naive")  # the chain kernel's arithmetic
            continue
        if force == "chain":
            modes.append("gauss")
            continue
        m, k, nn = step_dims(st)
        strassen_gain = (
            _strassen_saving_s(cost_model, m, k, nn)
            if strassen_eligible(m, k, nn)
            else float("-inf")
        )
        transpose_gain = (
            _fused_transpose_saving_s(cost_model, st)
            if fused_transpose_step_eligible(st)
            else float("-inf")
        )
        if strassen_gain <= 0.0 and transpose_gain <= 0.0:
            modes.append(default_step_mode(st))
        elif strassen_gain >= transpose_gain:
            modes.append("strassen")
        else:
            modes.append("fused_transpose")
    if pmodes and dot_precision_forced() is None and precision_force is None:
        # never STACK the auto bf16x3 rung on a Strassen step: the
        # budget check models the plain-dot rung only, and Strassen's
        # extra add/sub passes amplify the error past both documented
        # rungs. A forced TNC_TPU_DOT_PRECISION is the explicit A/B —
        # it stays global (its parity oracle is the gate).
        pmodes = tuple(
            "" if modes[i] == "strassen" else p
            for i, p in enumerate(pmodes)
        )
        if not any(pmodes):
            pmodes = ()
    return KernelPolicy(tuple(modes), chains, pmodes)


def step_bucket(step) -> str:
    """Shape bucket of one step for MFU reporting — policy-independent
    so buckets stay comparable across runs: ``stem`` (clears the
    Strassen crossover), ``small`` (under the fused kernel's flop
    floor, the dispatch-dominated regime), ``medium`` (the rest)."""
    from tnc_tpu.ops.pallas_complex import MIN_FLOPS
    from tnc_tpu.ops.program import step_dims, step_flops
    from tnc_tpu.ops.strassen import strassen_eligible

    m, k, n = step_dims(step)
    if strassen_eligible(m, k, n):
        return "stem"
    if 2 * step_flops(step) < MIN_FLOPS:
        return "small"
    return "medium"


def effective_step_flops(step, mode: str) -> float:
    """A step's flop count credited for the kernel mode that ran it
    (same ``k*m*n`` complex units as :func:`tnc_tpu.ops.program.
    step_flops`, scaled by :data:`EFFECTIVE_FLOP_FACTOR`) — the number
    MFU should divide by so algorithmically-cheaper kernels don't
    inflate it."""
    from tnc_tpu.ops.program import step_flops

    return step_flops(step) * EFFECTIVE_FLOP_FACTOR.get(mode, 1.0)


def kernel_plan_summary(
    program: ContractionProgram,
    policy: KernelPolicy | None = None,
    dtype_bytes: float = 8.0,
) -> dict:
    """JSON-able per-bucket summary of a program under a policy: step
    counts, naive vs effective (mode-credited) flops, the mode and
    dot-precision mixes, predicted HBM bytes under the naive prep+dot
    path vs under the planned modes (the fused transpose rung's
    deleted pass shows up as ``pred_bytes_planned <
    pred_bytes_naive`` on transpose-carrying buckets — the invariant
    ``scripts/perf_gate.py`` enforces), and the dispatch count
    (chains collapse to one). ``lowering`` is the whole program by the
    arithmetic each step resolves to (:func:`resolved_step_mode`): steps
    and the shares of steps and of multiply-adds under each mode — how
    far the program runs in the ``block`` form. ``prep`` is the program by
    where the prep of each step's streamed operand ends
    (:func:`step_prep_form`: ``tiled`` or ``matrix``): steps and the
    shares of steps and of streamed elements. ``fusion`` is what stem
    fusion did to the plan the program was built from
    (:func:`tnc_tpu.contractionpath.stem_fusion.fuse_stem_operands`'s
    report, carried as ``program.fusion``): groups, large steps that
    became small products, and large steps, the elements they stream
    and the multiply-adds of a slice as ``[before, after]``; ``None``
    for a program whose plan never passed it. ``dtype_bytes`` defaults
    to the device path's f32 split-pair width (8 B per complex element).
    The static side of ``bench.py``'s per-bucket MFU report."""
    if policy is None:
        policy = plan_kernels(program)
    from tnc_tpu.ops.program import (
        operand_prep,
        step_elems,
        step_flops,
        step_prep_elems,
        streamed_side,
    )

    buckets: dict[str, dict] = {}
    lowering: dict[str, dict] = {}
    prep: dict[str, dict] = {}
    for i, st in enumerate(program.steps):
        b = buckets.setdefault(
            step_bucket(st),
            {
                "steps": 0,
                "flops": 0.0,
                "effective_flops": 0.0,
                "modes": {},
                "precision": {},
                "transpose_steps": 0,
                "pred_bytes_naive": 0.0,
                "pred_bytes_planned": 0.0,
            },
        )
        mode = policy.modes[i]
        resolved = resolved_step_mode(st, mode)
        low = lowering.setdefault(resolved, {"steps": 0, "flops": 0.0})
        low["steps"] += 1
        low["flops"] += step_flops(st)
        form = prep.setdefault(
            step_prep_form(st, mode), {"steps": 0, "elems": 0.0}
        )
        form["steps"] += 1
        form["elems"] += float(
            math.prod(operand_prep(st, streamed_side(st))[0])
        )
        b["steps"] += 1
        b["flops"] += step_flops(st)
        b["effective_flops"] += effective_step_flops(st, resolved)
        b["modes"][mode] = b["modes"].get(mode, 0) + 1
        rung = policy.precision_mode(i) or "default"
        b["precision"][rung] = b["precision"].get(rung, 0) + 1
        if step_prep_elems(st) > 0.0:
            b["transpose_steps"] += 1
        naive_in, naive_out = step_elems(st)
        plan_in, plan_out = step_elems(st, mode=resolved)
        b["pred_bytes_naive"] += (naive_in + naive_out) * dtype_bytes
        b["pred_bytes_planned"] += (plan_in + plan_out) * dtype_bytes
    for b in buckets.values():
        b["flops"] = float(f"{b['flops']:.4e}")
        b["effective_flops"] = float(f"{b['effective_flops']:.4e}")
        b["pred_bytes_naive"] = float(f"{b['pred_bytes_naive']:.4e}")
        b["pred_bytes_planned"] = float(f"{b['pred_bytes_planned']:.4e}")
        if b["steps"]:
            b["pred_bytes_per_step_naive"] = float(
                f"{b['pred_bytes_naive'] / b['steps']:.4e}"
            )
            b["pred_bytes_per_step_planned"] = float(
                f"{b['pred_bytes_planned'] / b['steps']:.4e}"
            )
    total_flops = sum(low["flops"] for low in lowering.values())
    for low in lowering.values():
        low["step_share"] = round(low["steps"] / len(program.steps), 4)
        low["flops_share"] = round(low.pop("flops") / max(total_flops, 1.0), 4)
    total_elems = sum(form["elems"] for form in prep.values())
    for form in prep.values():
        form["step_share"] = round(form["steps"] / len(program.steps), 4)
        form["elems_share"] = round(
            form.pop("elems") / max(total_elems, 1.0), 4
        )
    return {
        "buckets": buckets,
        "lowering": lowering,
        "prep": prep,
        "fusion": dict(program.fusion) if program.fusion else None,
        "dispatches": policy.dispatch_count(),
        "chains": len(policy.chains),
        "chained_steps": len(policy.chained_steps()),
    }


def _run_chain_split(steps, buffers, precision, precision_mode="",
                     interpret=False):
    """Execute a grouped run of steps as ONE fused Pallas dispatch.

    Non-carried operands are prepped to contract-dim-leading 2-D
    outside the kernel (XLA-land, where transposes are free to fuse);
    the carried value flows through the kernel in VMEM. Returns the
    final (re, im) pair reshaped to the last step's ``out_store``."""
    import jax.numpy as jnp

    from tnc_tpu.ops.backends import _prep_operand
    from tnc_tpu.ops.pallas_complex import ChainLink, fused_chain_kl

    prec = _resolve_step_precision(precision, precision_mode)

    def prep_kl(pair, view, perm, dot_shape, ops, cfirst):
        r = _prep_operand(jnp, pair[0], view, perm, dot_shape, ops)
        i = _prep_operand(jnp, pair[1], view, perm, dot_shape, ops)
        return _as_kl(jnp, r, dot_shape, cfirst), _as_kl(
            jnp, i, dot_shape, cfirst
        )

    head = steps[0]
    a = prep_kl(
        buffers[head.lhs], head.a_view, head.a_perm, head.a_dot,
        head.a_ops, head.a_cfirst,
    )
    b = prep_kl(
        buffers[head.rhs], head.b_view, head.b_perm, head.b_dot,
        head.b_ops, head.b_cfirst,
    )
    first, second = (b, a) if head.swap else (a, b)
    first_ops = (first[0], first[1], second[0], second[1])

    link_ops = []
    links = []
    run_slot = head.lhs
    for st in steps[1:]:
        carried_a = st.lhs == run_slot
        if carried_a:
            c_pair, c_view, c_perm, c_dot, c_ops, c_cfirst = (
                buffers[st.rhs], st.b_view, st.b_perm, st.b_dot,
                st.b_ops, st.b_cfirst,
            )
            carried_dot, carried_cfirst = st.a_dot, st.a_cfirst
        else:
            c_pair, c_view, c_perm, c_dot, c_ops, c_cfirst = (
                buffers[st.lhs], st.a_view, st.a_perm, st.a_dot,
                st.a_ops, st.a_cfirst,
            )
            carried_dot, carried_cfirst = st.b_dot, st.b_cfirst
        link_ops.append(
            prep_kl(c_pair, c_view, c_perm, c_dot, c_ops, c_cfirst)
        )
        k = int(carried_dot[0]) if carried_cfirst else int(carried_dot[-1])
        f = int(math.prod(carried_dot)) // max(k, 1)
        carried_shape = (k, f) if carried_cfirst else (f, k)
        k_axis = 0 if carried_cfirst else 1
        carried_first = (not carried_a) if st.swap else carried_a
        links.append(ChainLink(carried_first, carried_shape, k_axis))
        run_slot = st.lhs

    re, im = fused_chain_kl(
        first_ops, link_ops, links, interpret=interpret, precision=prec
    )
    out_store = steps[-1].out_store
    return re.reshape(out_store), im.reshape(out_store)


def run_chain_split(xp, steps, buffers, precision=None, precision_mode="",
                    interpret=False):
    """Execute one chain group with full buffer bookkeeping — the
    fused dispatch on device, the sequential naive loop on the host
    oracle (bit-identical arithmetic). ``precision_mode`` is the
    chain's dot-precision rung (one rung per chain — the policy's
    head-step entry). Mutates ``buffers`` the same way the sequential
    loop would. A chain that was planned and cannot trace or compile
    fails the run."""
    if xp is np:
        for st in steps:
            buffers[st.lhs] = apply_step_split(
                xp, buffers[st.lhs], buffers[st.rhs], st, precision,
                mode="naive", precision_mode=precision_mode,
            )
            buffers[st.rhs] = None
        return buffers[steps[-1].lhs]
    out = _run_chain_split(
        steps, buffers, precision, precision_mode, interpret
    )
    for st in steps:
        buffers[st.rhs] = None
    buffers[steps[-1].lhs] = out
    return out


def apply_steps_split(
    xp,
    steps: Sequence[PairStep],
    state,
    precision=None,
    policy: KernelPolicy | None = None,
    interpret: bool = False,
    numbers: Sequence[int] | None = None,
) -> None:
    """The one walker of split-complex steps: run ``steps`` in order
    over ``state`` (a list or dict, slot -> (real, imag) pair), in
    place; a consumed slot is left ``None``. ``policy`` (a
    :class:`KernelPolicy` planned over exactly these ``steps``, spans
    indexed relative to them) fuses chains into single Pallas
    dispatches and promotes steps per the kernel ladder; None runs
    every step under the forcing override, else as its shape decides
    (:func:`default_step_mode`). Between two ``block`` steps a value
    stays ONE array (``carry``, see :func:`apply_step_split`: ``(2,) +
    stored``, or the image a tiled step wrote where the next streams
    it); every other lowering takes and hands back
    a pair, and so does a step whose result outlives the walk, so a
    caller sees pairs alone.
    ``interpret``: see :func:`apply_step_split`. ``numbers``: the
    number of each step in the step list of the program being traced,
    for its named scope (default: its index here, right where these
    ``steps`` are all the jitted module runs)."""
    if numbers is None:
        numbers = range(len(steps))
    chain_end = (
        {s: e for s, e in policy.chains} if policy is not None else {}
    )
    # a result is carried only to a later step of this walk: what
    # outlives the walk leaves as the pair a caller expects
    reader: dict[int, int] = {}  # slot -> the next step that reads it
    carried: list[bool | str] = [False] * len(steps)
    for i in range(len(steps) - 1, -1, -1):
        step = steps[i]
        j = reader.get(step.lhs)
        if j is not None:  # a chained reader's mode is ``naive``
            carried[i] = _carry_form(
                steps[j], step.lhs,
                policy.modes[j] if policy is not None else None,
            )
        reader[step.lhs] = reader[step.rhs] = i
    i = 0
    while i < len(steps):
        end = chain_end.get(i)
        if end is not None:
            for st in steps[i:end]:  # a chain reads pairs
                for slot in (st.lhs, st.rhs):
                    if state[slot] is not None:
                        state[slot] = _as_pair(state[slot])
            with _chain_scope(xp, steps[i], numbers[i]):
                run_chain_split(
                    xp, steps[i:end], state, precision,
                    precision_mode=policy.precision_mode(i),
                    interpret=interpret,
                )
            i = end
            continue
        step = steps[i]
        state[step.lhs] = apply_step_split(
            xp, state[step.lhs], state[step.rhs], step, precision,
            mode=policy.modes[i] if policy is not None else None,
            precision_mode=(
                policy.precision_mode(i) if policy is not None else None
            ),
            interpret=interpret, carry=carried[i], number=numbers[i],
        )
        state[step.rhs] = None
        i += 1


def _chain_scope(xp, head, number: int):
    """The scope of a chain: one fused dispatch, named after its head
    step (mode ``chain``; the steps it swallows get no scope)."""
    if xp is np:
        return contextlib.nullcontext()
    from tnc_tpu.obs import op_table
    from tnc_tpu.ops.program import step_size_class

    scope = op_table.step_scope_name(
        number, step_size_class(head), "chain", "matrix"
    )
    op_table.note_step(number, scope)
    return op_table.named_scope(scope)


def run_steps_split(
    xp,
    program: ContractionProgram,
    buffers: list[tuple[Any, Any] | None],
    precision=None,
    policy: KernelPolicy | None = None,
    interpret: bool = False,
):
    """Split-complex analogue of ``backends._run_steps``: a whole
    program through :func:`apply_steps_split`; the result is a
    (real, imag) pair in **stored** shape (callers reshape to
    ``result_shape`` on the host)."""
    apply_steps_split(
        xp, program.steps, buffers, precision, policy, interpret
    )
    return buffers[program.result_slot]
