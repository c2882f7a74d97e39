"""Strassen-style contraction for the large slice-invariant stem GEMMs.

Tensor contraction is implicit matmul, and at the stem-GEMM shapes the
hoist pass isolates (``ops/hoist.py`` — big, square-ish, power-of-two
dims) a single Strassen recursion level gives a measurable speedup
(PAPERS.md, arXiv:1704.03092: one level ≈ 7/8 of the multiplies for a
few extra elementwise passes, profitable once the dims clear ~2^11).

Composition with split-complex arithmetic: a complex product lowers to
3 real GEMMs via the Gauss identity (``ops/split_complex.gauss_matmul``)
and each of those 3 runs one Strassen level — **3×7 = 21 half-size real
sub-GEMMs** against the naive lowering's 4 full GEMMs (= 32 half-size
multiply units): a 21/32 ≈ 0.66× multiply count. That factor is also
the *effective-flop credit* the benchmark applies so MFU numbers stay
comparable across kernel modes (``bench.py`` kernel buckets).

Layout convention matches the step compiler's dot layout and the fused
Pallas kernel: operands arrive contract-dim-leading, ``A: (K, M)``,
``B: (K, N)``, result ``AᵀB: (M, N)``. Written against that layout the
Strassen block sums are sums of contiguous ``(K/2, M/2)`` quadrants —
no operand is ever transposed; the transpose lives inside the
``dot_general`` contracting-dims spec (dim 0 × dim 0).

Numerics: Strassen's extra additions mix operand magnitudes before the
products, so rounding error grows a small constant factor over the
naive dot (same failure family as the Gauss/Karatsuba instability —
see ``split_complex.complex_mult_forced``). The parity pins live in
``tests/test_strassen.py``; the documented tolerance rungs vs the
complex128 numpy oracle are **2e-5 relative (float32)** and **1e-12
relative (float64)** at one recursion level.
"""

from __future__ import annotations

#: one Strassen level only pays off once every matricized dim clears
#: this floor (calibrated crossover: below it the 15 extra elementwise
#: passes over quadrant-sized buffers cost more than the saved eighth
#: of the multiplies; 2^11 per dim ≈ the stem-GEMM regime).
STRASSEN_MIN_DIM = 1 << 11

#: "square-ish" guard: beyond this aspect ratio the problem is really a
#: panel GEMM — bandwidth-bound, where Strassen's extra passes hurt.
STRASSEN_MAX_ASPECT = 4.0

#: multiply-count credit of one gauss+strassen level vs the naive 4-dot
#: complex lowering: 3 Gauss products × 7 half-size sub-GEMMs = 21
#: half-units against naive's 4 GEMMs × 8 half-units = 32.
GAUSS_STRASSEN_FLOP_FACTOR = 21.0 / 32.0


def strassen_eligible(
    m: int,
    k: int,
    n: int,
    min_dim: int | None = None,
    max_aspect: float | None = None,
) -> bool:
    """Can one Strassen level run an ``(m, k) @ (k, n)`` problem
    profitably? Every dim must halve evenly (program dims are powers of
    two, so this only excludes degenerate odd shapes), clear the
    crossover floor, and the problem must be square-ish.

    >>> strassen_eligible(4096, 2048, 4096)
    True
    >>> strassen_eligible(4096, 1024, 4096)    # K below the crossover
    False
    >>> strassen_eligible(1 << 16, 2048, 2048)  # panel, not square-ish
    False
    >>> strassen_eligible(2049, 2048, 2048)     # odd dim cannot halve
    False
    """
    if min_dim is None:
        min_dim = STRASSEN_MIN_DIM
    if max_aspect is None:
        max_aspect = STRASSEN_MAX_ASPECT
    dims = (m, k, n)
    if any(d % 2 for d in dims):
        return False
    lo, hi = min(dims), max(dims)
    if lo < min_dim:
        return False
    return hi <= max_aspect * lo


def _kl_dot(xp, precision):
    """The base multiply for the (K, M)×(K, N) layout: contract dim 0
    of both operands. numpy has no dot_general; ``x.T @ y`` is the same
    contraction."""
    if xp.__name__.startswith("numpy"):
        return lambda x, y: x.T @ y
    from jax import lax

    def dot(x, y):
        return lax.dot_general(
            x, y, (((0,), (0,)), ((), ())), precision=precision
        )

    return dot


def strassen_dot_kl(xp, a, b, dot=None, precision=None):
    """One Strassen level of ``aᵀ @ b`` with ``a: (K, M)``, ``b: (K, N)``.

    Quadrants are taken in the *stored* kl layout — with ``X = aᵀ`` the
    logical Strassen operand, ``X[i][j] == a[j][i]ᵀ``, so every block
    sum is a sum of contiguous ``a`` quadrants and the only transposes
    are inside the 7 sub-products' contracting-dims spec. ``dot``
    overrides the sub-product kernel (the Pallas fused path could slot
    in here); default contracts dim 0 × dim 0 via matmul/dot_general.

    >>> import numpy as np
    >>> rng = np.random.default_rng(0)
    >>> a, b = rng.standard_normal((8, 6)), rng.standard_normal((8, 4))
    >>> np.allclose(strassen_dot_kl(np, a, b), a.T @ b)
    True
    """
    k, m = a.shape
    _, n = b.shape
    if k % 2 or m % 2 or n % 2:
        raise ValueError(f"shape (K={k}, M={m}, N={n}) does not halve")
    if dot is None:
        dot = _kl_dot(xp, precision)
    k2, m2, n2 = k // 2, m // 2, n // 2
    # a-quadrants in kl layout: X11 = a11ᵀ, X12 = a21ᵀ, X21 = a12ᵀ, ...
    a11, a21 = a[:k2, :m2], a[:k2, m2:]
    a12, a22 = a[k2:, :m2], a[k2:, m2:]
    b11, b12 = b[:k2, :n2], b[:k2, n2:]
    b21, b22 = b[k2:, :n2], b[k2:, n2:]
    # X11=a11ᵀ X12=a12ᵀ(from a[k2:, :m2]).. careful: X = aᵀ is (M, K);
    # X[row block i][col block j] = a[col block j][row block i]ᵀ:
    #   X11 = a[:k2, :m2]ᵀ   X12 = a[k2:, :m2]ᵀ
    #   X21 = a[:k2, m2:]ᵀ   X22 = a[k2:, m2:]ᵀ
    x11, x12 = a11, a12
    x21, x22 = a21, a22
    p1 = dot(x11 + x22, b11 + b22)  # (X11+X22)(Y11+Y22)
    p2 = dot(x21 + x22, b11)        # (X21+X22)Y11
    p3 = dot(x11, b12 - b22)        # X11(Y12-Y22)
    p4 = dot(x22, b21 - b11)        # X22(Y21-Y11)
    p5 = dot(x11 + x12, b22)        # (X11+X12)Y22
    p6 = dot(x21 - x11, b11 + b12)  # (X21-X11)(Y11+Y12)
    p7 = dot(x12 - x22, b21 + b22)  # (X12-X22)(Y21+Y22)
    c11 = p1 + p4 - p5 + p7
    c12 = p3 + p5
    c21 = p2 + p4
    c22 = p1 - p2 + p3 + p6
    top = xp.concatenate([c11, c12], axis=1)
    bot = xp.concatenate([c21, c22], axis=1)
    return xp.concatenate([top, bot], axis=0)


def gauss_strassen_dot_kl(xp, ar, ai, br, bi, precision=None):
    """``(re, im)`` of ``(ar + i·ai)ᵀ @ (br + i·bi)`` via the Gauss
    3-mult complex identity with one Strassen level per real product:
    3×7 = 21 half-size real sub-GEMMs against the naive lowering's 4
    full dots. Same kl layout as :func:`strassen_dot_kl`.

    >>> import numpy as np
    >>> rng = np.random.default_rng(1)
    >>> ar, ai = rng.standard_normal((8, 6)), rng.standard_normal((8, 6))
    >>> br, bi = rng.standard_normal((8, 4)), rng.standard_normal((8, 4))
    >>> re, im = gauss_strassen_dot_kl(np, ar, ai, br, bi)
    >>> want = (ar + 1j * ai).T @ (br + 1j * bi)
    >>> np.allclose(re + 1j * im, want)
    True
    """
    dot = _kl_dot(xp, precision)
    k1 = strassen_dot_kl(xp, ar + ai, br, dot=dot)
    k2 = strassen_dot_kl(xp, ar, bi - br, dot=dot)
    k3 = strassen_dot_kl(xp, ai, br + bi, dot=dot)
    return k1 - k3, k1 + k2
