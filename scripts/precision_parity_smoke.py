#!/usr/bin/env python
"""CI smoke: the bf16x3 dot-precision rung's numerical contract, on CPU.

``precision="high"`` (3-pass bf16x3 MXU emulation) may only be
promoted after a slice-subset parity check against the oracle on the
device. This smoke is the CI-runnable half of that gate: it *emulates* the
bf16x3 recomposition explicitly (split each f32 operand into bf16
(hi, mid) terms, keep the hi·hi + hi·mid + mid·hi cross products,
accumulate in f32 — the arithmetic the 3-pass mode performs) and
measures it against the float64 split-complex oracle on one
representative contraction length per shape bucket:

- the measured relative error must sit under the DOCUMENTED rung
  (``split_complex.HIGH_PRECISION_STEP_REL`` with 4x margin) for every
  bucket — the constant ``plan_precision_modes`` budgets promotions
  against must stay an upper bound in spirit, not a stale guess;
- the 1-pass bf16 truncation (``precision="default"``) must FAIL the
  amplitude target on the same shapes — pinning that the ladder's
  ordering (default < high < highest) is real, so a promotion decision
  between rungs is meaningful;
- plain f32 (the ``highest``-rung proxy on CPU) must beat bf16x3 —
  the ladder is monotone.

What this does NOT validate: the libtpu pass count of
``lax.Precision.HIGH`` on a given device generation — that needs a
measured A/B on the chip, which has not been run. The smoke pins the
*numerical contract* the promotion logic budgets against.

Prints
``promote precision=high: ok`` when every bucket passes its rung.
Wired into scripts/check.sh.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import numpy as np  # noqa: E402

#: representative contraction length per shape bucket (the error of a
#: recomposed dot grows with the accumulation length k, not with the
#: free dims — m = n = 256 keeps the float64 oracle CI-cheap), plus a
#: FIXED rng seed per bucket: a CI gate must measure the same matrices
#: every run (str hash() is PYTHONHASHSEED-randomized — never seed
#: from it)
BUCKET_K = {"small": (64, 101), "medium": (512, 102), "stem": (2048, 103)}

#: the amplitude-parity target the ladder serves (BASELINE contract)
AMPLITUDE_TARGET = 1e-5


def _bf16_split(x, jnp):
    """f32 → (hi, mid) bf16 terms, both carried as f32 for the dots."""
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    mid = (x - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, mid


def bf16x3_matmul(x, y, jnp):
    """The 3-pass bf16x3 recomposition: hi·hi + hi·mid + mid·hi,
    accumulated in f32 — the arithmetic ``lax.Precision.HIGH`` runs on
    the MXU, emulated explicitly so CPU CI can measure its error."""
    xh, xm = _bf16_split(x, jnp)
    yh, ym = _bf16_split(y, jnp)
    return xh @ yh + (xh @ ym + xm @ yh)


def bf16x1_matmul(x, y, jnp):
    """The 1-pass truncation (``precision="default"`` on the MXU)."""
    return (x.astype(jnp.bfloat16) @ y.astype(jnp.bfloat16)).astype(
        jnp.float32
    )


def _complex_split_dot(matmul, ar, ai, br, bi, jnp):
    """Naive 4-dot split-complex multiply through ``matmul`` — the
    kernel arithmetic whose dots the precision rung replaces."""
    re = matmul(ar, br, jnp) - matmul(ai, bi, jnp)
    im = matmul(ar, bi, jnp) + matmul(ai, br, jnp)
    return re, im


def run_bucket(name: str, k: int, seed: int, rung: float) -> dict:
    import jax.numpy as jnp

    from tnc_tpu.ops.split_complex import HIGH_PRECISION_STEP_REL

    rng = np.random.default_rng(seed)
    m = n = 256

    def f32(*shape):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32))

    ar, ai = f32(m, k), f32(m, k)
    br, bi = f32(k, n), f32(k, n)

    # float64 split oracle (the complex128 contract, split form)
    a64 = np.asarray(ar, dtype=np.float64) + 1j * np.asarray(
        ai, dtype=np.float64
    )
    b64 = np.asarray(br, dtype=np.float64) + 1j * np.asarray(
        bi, dtype=np.float64
    )
    want = a64 @ b64
    denom = float(np.abs(want).max())

    def err(matmul):
        re, im = _complex_split_dot(matmul, ar, ai, br, bi, jnp)
        got = np.asarray(re, dtype=np.float64) + 1j * np.asarray(
            im, dtype=np.float64
        )
        return float(np.abs(got - want).max() / denom)

    e_high = err(bf16x3_matmul)
    e_default = err(bf16x1_matmul)
    e_f32 = err(lambda x, y, _: x @ y)

    assert e_high < rung, (
        f"{name}: bf16x3 rel err {e_high:.2e} >= documented rung "
        f"{rung:.2e} (HIGH_PRECISION_STEP_REL="
        f"{HIGH_PRECISION_STEP_REL:.2e} went stale — remeasure before "
        "letting plan_precision_modes budget against it)"
    )
    assert e_default > AMPLITUDE_TARGET, (
        f"{name}: 1-pass bf16 rel err {e_default:.2e} unexpectedly "
        f"PASSES the {AMPLITUDE_TARGET} target — the ladder's ordering "
        "assumption broke; revisit the promotion logic"
    )
    assert e_f32 < e_high, (
        f"{name}: f32 ({e_f32:.2e}) is not tighter than bf16x3 "
        f"({e_high:.2e}) — the ladder is not monotone"
    )
    print(
        f"[precision smoke] {name:>6} (k={k:>4}): "
        f"default {e_default:.1e} (fails target, expected)  "
        f"high {e_high:.1e} < rung {rung:.1e}  f32 {e_f32:.1e} OK"
    )
    return {"high": e_high, "default": e_default, "f32": e_f32}


def main() -> int:
    from tnc_tpu.ops.split_complex import HIGH_PRECISION_STEP_REL

    rung = 4.0 * HIGH_PRECISION_STEP_REL  # documented rung, 4x margin
    for name, (k, seed) in BUCKET_K.items():
        run_bucket(name, k, seed, rung)
    print(
        "[precision smoke] promote precision=high: ok "
        f"(all buckets under {rung:.1e}; the pass-count A/B on the chip "
        "has not been run)"
    )
    print("[precision smoke] PASSED")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
