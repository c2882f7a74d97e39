"""Unit tier for ``bench.py``'s pure helpers (the config marker, the
precision ladder) and for what a run may not hide: the compile-cache
rule, the HBM budget of an unknown accelerator, and a benchmark without
a chip."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


def test_tuned_default_missing_marker(tmp_path):
    assert (
        bench._tuned_default(
            "precision", "float32", ("float32", "high"),
            marker_path=str(tmp_path / "nope.json"),
        )
        == "float32"
    )


def test_tuned_default_reads_marker_and_validates(tmp_path):
    marker = tmp_path / "best_config.json"
    marker.write_text(json.dumps({"precision": "high", "complex_mult": "quux"}))
    assert (
        bench._tuned_default(
            "precision", "float32", ("float32", "high"), marker_path=str(marker)
        )
        == "high"
    )
    # unknown values never escape the allowed set
    assert (
        bench._tuned_default(
            "complex_mult", "naive", ("naive", "gauss", "fused"),
            marker_path=str(marker),
        )
        == "naive"
    )
    marker.write_text("not json{")
    assert (
        bench._tuned_default(
            "precision", "float32", ("float32", "high"), marker_path=str(marker)
        )
        == "float32"
    )


def test_bench_without_a_chip_is_an_error():
    """No accelerator and no BENCH_FORCE_CPU=1: exit 1 with an error
    line — never a CPU number under a device metric's name."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {
        k: v for k, v in os.environ.items() if not k.startswith("BENCH_")
    }
    env.update(JAX_PLATFORMS="cpu", BENCH_CONFIG="ghz3")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "bench.py")],
        capture_output=True, text=True, env=env, cwd=root, timeout=300,
    )
    assert proc.returncode == 1
    record = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "BENCH_FORCE_CPU" in record["error"]
    assert "device" not in record


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_directory_rule(monkeypatch, tmp_path, placed):
    """A cache directory placed from outside stands and the code sets
    none; otherwise the fixed in-checkout path."""
    import jax

    from tnc_tpu.utils import compile_cache

    calls = []
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: calls.append((k, v))
    )
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    set_dirs = [v for k, v in calls if k == "jax_compilation_cache_dir"]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if placed:
        assert got == str(tmp_path) and set_dirs == []
    else:
        assert got == os.path.join(root, ".cache", "jax_cache")
        assert set_dirs == [got]
    assert got == compile_cache.enable_compile_cache()  # fixed, no pid/time


def test_device_hbm_bytes_raises_for_unknown_accelerator(monkeypatch):
    from tnc_tpu.ops.budget import device_hbm_bytes

    class Dev:
        def __init__(self, platform, kind, stats=None):
            self.platform, self.device_kind, self._stats = platform, kind, stats

        def memory_stats(self):
            return self._stats

    monkeypatch.delenv("TNC_TPU_HBM_BYTES", raising=False)
    with pytest.raises(ValueError, match="unknown accelerator"):
        device_hbm_bytes(Dev("tpu", "TPU v9 mega"))
    assert device_hbm_bytes(Dev("tpu", "TPU v5 lite")) == 16 << 30
    assert device_hbm_bytes(
        Dev("tpu", "TPU v9 mega", {"bytes_limit": 123})
    ) == 123
    assert device_hbm_bytes(Dev("cpu", "cpu")) == 64 << 30


def test_resolve_precision_ladder():
    """The device dot-precision ladder: default (1-pass bf16) < high
    (bf16x3) < float32/anything-else (bf16x6 HIGHEST)."""
    from jax import lax

    from tnc_tpu.ops.split_complex import _resolve_precision

    assert _resolve_precision(None) is None
    assert _resolve_precision("default") is None
    assert _resolve_precision("high") is lax.Precision.HIGH
    assert _resolve_precision("float32") is lax.Precision.HIGHEST
    assert _resolve_precision("anything") is lax.Precision.HIGHEST


def test_bind_resident_repeat_stable():
    """Donation-off contract: the bound executable reuses resident
    buffers across calls bit-identically (the small-network steady-state
    timing discipline)."""
    import numpy as np

    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.ops.backends import JaxBackend, NumpyBackend
    from tnc_tpu.ops.program import build_program, flat_leaf_tensors
    from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor
    from tnc_tpu.tensornetwork.tensordata import TensorData

    rng = np.random.default_rng(0)
    tn = CompositeTensor(
        [
            LeafTensor([0, 1], [4, 4], TensorData.matrix(rng.standard_normal((4, 4)))),
            LeafTensor([1, 2], [4, 4], TensorData.matrix(rng.standard_normal((4, 4)))),
            LeafTensor([2, 0], [4, 4], TensorData.matrix(rng.standard_normal((4, 4)))),
        ]
    )
    path = Greedy(OptMethod.GREEDY).find_path(tn).replace_path()
    program = build_program(tn, path)
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]
    bound = JaxBackend(dtype="complex64").bind_resident(program, arrays)
    first = np.asarray(bound())
    for _ in range(3):
        np.testing.assert_array_equal(np.asarray(bound()), first)
    want = NumpyBackend(np.complex128).execute(program, arrays)
    np.testing.assert_allclose(
        first.reshape(program.result_shape), want, rtol=1e-5, atol=1e-6
    )


def test_ssa_to_replace_matches_canonical():
    # hand-derived replace-left expectation (NOT recomputed through the
    # helper's own delegate): ssa ids 4,5,6 land in slots 0,0,0
    assert bench._ssa_to_replace([(0, 2), (4, 1), (5, 3)]) == [
        (0, 2),
        (0, 1),
        (0, 3),
    ]
