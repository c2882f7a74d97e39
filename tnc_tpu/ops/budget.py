"""HBM budget modeling and enforcement for sliced execution.

The reference computes memory requirements analytically before running
(``contractionpath/contraction_cost.rs:254-264``,
``book/src/parallelization.md`` — "memory requirements can already be
computed theoretically") and the benchmark picks configurations that fit
node RAM. On TPU the binding constraint is tighter — a single chip's HBM
— and the *physical* footprint differs from the logical element count
because f32 buffers are stored in (sublane × 128-lane) tiles: a trailing
dim below 128 pads up to it.

This module is the executor-side guardrail (an early benchmark round
compiled a 34 GB padded buffer into 16 GB of HBM): it models
the padded footprint of a compiled program step by step and clamps the
chunked executor's ``slice_batch`` — or reports that a deeper slicing
target is needed — so the plan provably fits before anything is
dispatched to the device.
"""

from __future__ import annotations

import logging
import math
import os
from dataclasses import dataclass

from tnc_tpu import obs

logger = logging.getLogger(__name__)

_LANE = 128

# device_kind substring → HBM bytes (public spec sheets)
_HBM_BYTES = {
    "v2": 8 << 30,
    "v3": 16 << 30,
    "v4": 32 << 30,
    "v5 lite": 16 << 30,
    "v5e": 16 << 30,
    "v5p": 95 << 30,
    "v6 lite": 32 << 30,
    "v6e": 32 << 30,
}


def device_hbm_bytes(device=None) -> int:
    """Usable accelerator memory for ``device`` (default: first device).

    Order: ``TNC_TPU_HBM_BYTES`` env override → live ``memory_stats()``
    → device-kind table. An accelerator that reports no stats and whose
    kind is not in the table raises — a guessed budget would let a plan
    through that does not fit. The CPU backend gets a host-RAM-class
    64 GiB (tests).
    """
    env = os.environ.get("TNC_TPU_HBM_BYTES")
    if env:
        return int(env)
    if device is None:
        import jax

        device = jax.devices()[0]
    stats = device.memory_stats()
    if stats and "bytes_limit" in stats:
        return int(stats["bytes_limit"])
    kind = getattr(device, "device_kind", "").lower()
    for tag, n in _HBM_BYTES.items():
        if tag in kind:
            return n
    if device.platform == "cpu":
        return 64 << 30  # host RAM-class budget for the CPU backend
    raise ValueError(
        f"unknown accelerator {device.platform!r} kind "
        f"{getattr(device, 'device_kind', '')!r}: it reports no "
        "memory_stats() and is not in the HBM table "
        "(tnc_tpu.ops.budget._HBM_BYTES); add it there or set "
        "TNC_TPU_HBM_BYTES"
    )


def padded_elems(shape: tuple[int, ...]) -> int:
    """Tile-padded element count of an f32 buffer: the minor dim pads up
    to 128 (XLA shrinks sublane tiles, so the second-minor does not pad).

    >>> padded_elems((4, 128)), padded_elems((4, 2)), padded_elems((1024,))
    (512, 512, 1024)
    """
    if not shape:
        return 1
    n = math.prod(shape[:-1]) if len(shape) > 1 else 1
    minor = shape[-1]
    return n * (-(-minor // _LANE) * _LANE if minor < _LANE else minor)


@dataclass(frozen=True)
class PeakEstimate:
    peak_bytes: int  # modeled peak HBM of one slice-batch execution
    peak_step: int  # step index at the peak
    bytes_per_batch_unit: int  # marginal bytes per +1 slice in the batch


def program_peak_bytes(
    program,
    *,
    split_complex: bool = True,
    dtype_bytes: int = 4,
    batch: int = 1,
) -> PeakEstimate:
    """Model the padded peak HBM of executing ``program`` with a leading
    slice-batch of ``batch``.

    Per step the working set is: all live stored buffers, both post-perm
    operand materializations, the dot output, and (split mode) one extra
    output-sized Gauss temporary (k1 lives while k2/k3 are built).
    """
    parts = 2 if split_complex else 1
    per_elem = dtype_bytes * parts

    live: dict[int, int] = {}
    for slot in range(program.num_inputs):
        live[slot] = 0  # leaf shapes are tiny; counted as free
    # leaves: caller may refine; treat as negligible (gates) but keep a
    # floor of one tile each
    leaf_bytes = program.num_inputs * 8 * _LANE * per_elem

    peak = leaf_bytes
    peak_step = -1
    for i, st in enumerate(program.steps):
        out = padded_elems(st.out_store)
        working = (
            sum(live.values())
            + padded_elems(tuple(st.a_dot))
            + padded_elems(tuple(st.b_dot))
            + out * (2 if split_complex else 1)  # dot out + gauss temp
        )
        cur = leaf_bytes + working * per_elem * batch
        if cur > peak:
            peak = cur
            peak_step = i
        live[st.lhs] = out
        live.pop(st.rhs, None)

    unit = (peak - leaf_bytes) // max(batch, 1)
    return PeakEstimate(int(peak), peak_step, int(unit))


def clamp_slice_batch(
    program,
    requested_batch: int,
    *,
    device=None,
    split_complex: bool = True,
    dtype_bytes: int = 4,
    safety: float = 0.75,
    hbm_bytes: int | None = None,
) -> int:
    """Largest batch ≤ ``requested_batch`` whose modeled peak fits in
    ``safety`` × HBM. Returns at least 1 (a batch of one either fits or
    the caller must slice deeper — see :func:`fits_hbm`)."""
    if hbm_bytes is None:
        hbm_bytes = device_hbm_bytes(device)
    budget = int(hbm_bytes * safety)
    est = program_peak_bytes(
        program, split_complex=split_complex, dtype_bytes=dtype_bytes, batch=1
    )
    if est.bytes_per_batch_unit <= 0:
        return max(1, requested_batch)
    fixed = est.peak_bytes - est.bytes_per_batch_unit  # leaf/tile floor
    fit = max(1, (budget - fixed) // est.bytes_per_batch_unit)
    clamped = max(1, min(requested_batch, fit))
    if obs.enabled():
        # modeled peak of the batch the executor will actually run — the
        # trace-side record of the budget decision
        obs.gauge_set(
            "hbm.modeled_peak_bytes",
            fixed + clamped * est.bytes_per_batch_unit,
        )
        obs.gauge_set("hbm.budget_bytes", budget)
        if clamped < requested_batch:
            obs.counter_add("hbm.batch_clamped")
    if clamped < requested_batch:
        logger.info(
            "HBM budget: slice batch clamped %d -> %d "
            "(peak/unit %.2f GiB, budget %.2f GiB)",
            requested_batch,
            clamped,
            est.bytes_per_batch_unit / 2**30,
            budget / 2**30,
        )
    return clamped


def fits_hbm(
    program,
    *,
    batch: int = 1,
    device=None,
    split_complex: bool = True,
    dtype_bytes: int = 4,
    safety: float = 0.75,
    hbm_bytes: int | None = None,
) -> bool:
    """Does the modeled peak of one ``batch``-slice execution fit?"""
    if hbm_bytes is None:
        hbm_bytes = device_hbm_bytes(device)
    est = program_peak_bytes(
        program, split_complex=split_complex, dtype_bytes=dtype_bytes, batch=batch
    )
    return est.peak_bytes <= hbm_bytes * safety


def compiled_peak_bytes(fn, arg_specs) -> int:
    """AOT-compile ``fn`` for ``arg_specs`` on the default device and
    return args+outputs+temps from XLA's memory analysis — the ground
    truth the model above approximates (used by the preflight tests)."""
    import jax

    compiled = jax.jit(fn).lower(*arg_specs).compile()
    ma = compiled.memory_analysis()
    return int(
        ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes
    )
