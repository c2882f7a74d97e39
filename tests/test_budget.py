"""HBM budget model: padded-footprint estimates, batch clamping, and the
compiled-peak preflight — the regression tests for an early benchmark OOM
(a 34 GB tile-padded allocation compiled into 16 GB of HBM)."""

import numpy as np

from tnc_tpu.ops.budget import (
    clamp_slice_batch,
    compiled_peak_bytes,
    device_hbm_bytes,
    fits_hbm,
    padded_elems,
    program_peak_bytes,
)
from tnc_tpu.ops.program import build_program
from tnc_tpu.contractionpath.contraction_path import ContractionPath
from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor
from tnc_tpu.tensornetwork.tensordata import TensorData


def test_padded_elems_minor_dim():
    assert padded_elems((4, 128)) == 4 * 128  # aligned: no pad
    assert padded_elems((4, 2)) == 4 * 128  # minor 2 -> 128
    assert padded_elems((1024,)) == 1024  # large 1-D: no pad
    assert padded_elems((2, 2, 256)) == 4 * 256
    assert padded_elems(()) == 1


def _chain_network(n: int, dim: int) -> tuple[CompositeTensor, ContractionPath]:
    """A matmul chain: n tensors of shape (dim, dim) sharing legs i,i+1."""
    rng = np.random.default_rng(5)
    tensors = []
    for i in range(n):
        t = LeafTensor([i, i + 1], [dim, dim])
        t.data = TensorData.matrix(
            rng.standard_normal((dim, dim)) + 1j * rng.standard_normal((dim, dim))
        )
        tensors.append(t)
    tn = CompositeTensor(tensors)
    path = ContractionPath.simple([(0, i) for i in range(1, n)])
    return tn, path


def test_peak_estimate_tracks_biggest_intermediate():
    tn, path = _chain_network(4, 64)
    program = build_program(tn, path)
    est = program_peak_bytes(program, split_complex=True, batch=1)
    # one 64x64 intermediate + operands: order of 64*64 elements * 8B,
    # plus the per-leaf tile floor
    assert est.peak_bytes > 64 * 64 * 8
    assert est.peak_bytes < 64 * 64 * 8 * 64
    # batch scales the marginal cost linearly
    est4 = program_peak_bytes(program, split_complex=True, batch=4)
    assert est4.peak_bytes > est.peak_bytes * 2


def test_clamp_slice_batch_respects_budget():
    tn, path = _chain_network(4, 256)
    program = build_program(tn, path)
    est = program_peak_bytes(program, batch=1)
    # a budget of ~3 batch-units must clamp an 8-batch request
    hbm = est.bytes_per_batch_unit * 4
    clamped = clamp_slice_batch(program, 8, hbm_bytes=hbm, safety=0.75)
    assert 1 <= clamped <= 3
    # a huge budget leaves the request untouched
    assert clamp_slice_batch(program, 8, hbm_bytes=1 << 40) == 8
    # fits_hbm agrees at the boundary
    assert fits_hbm(program, batch=clamped, hbm_bytes=hbm, safety=0.75)


def test_device_hbm_bytes_env_override(monkeypatch):
    monkeypatch.setenv("TNC_TPU_HBM_BYTES", str(123 << 20))
    assert device_hbm_bytes() == 123 << 20


def test_compiled_peak_close_to_model():
    """The analytic model must bound the XLA-compiled footprint within a
    small factor — the honest version of the claim in
    ``ops/backends.py`` that peak HBM matches the analytic prediction.
    On CPU there is no tile padding, so the model (which adds it) must
    be an upper bound-ish; on TPU (hardware tier) it must hold tightly.
    """
    import jax

    tn, path = _chain_network(5, 128)
    program = build_program(tn, path)

    from tnc_tpu.ops.split_complex import run_steps_split

    leaves = [t for t in tn.tensors]
    specs = tuple(
        (
            jax.ShapeDtypeStruct((128, 128), np.float32),
            jax.ShapeDtypeStruct((128, 128), np.float32),
        )
        for _ in leaves
    )

    def fn(buffers):
        import jax.numpy as jnp

        return run_steps_split(jnp, program, list(buffers), None)

    compiled = compiled_peak_bytes(fn, (specs,))
    est = program_peak_bytes(program, split_complex=True, batch=1)
    # modeled peak should be within ~4x of the compiled footprint either
    # way (XLA fuses/reuses buffers; the model is deliberately
    # conservative but must stay the same order of magnitude)
    assert compiled <= est.peak_bytes * 4
    assert est.peak_bytes <= compiled * 8
