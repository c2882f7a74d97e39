"""``chip_smoke.py`` rehearsed on the CPU: its phases are functions of
a circuit and a backend, so a 12-qubit circuit drives the same code the
chip run drives at 53 qubits."""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _circuit(depth=6, qubits=12, seed=42):
    from tnc_tpu.builders.sycamore_circuit import sycamore_circuit

    return sycamore_circuit(qubits, depth, np.random.default_rng(seed))


def test_final_line_format(smoke):
    line = smoke.final_line("tpu", "TPU v5 lite", 1)
    assert line == (
        '{"ok": true, "device": {"platform": "tpu", '
        '"kind": "TPU v5 lite", "count": 1}}'
    )
    assert json.loads(smoke.final_line("tpu", "TPU v5 lite", 4))["device"][
        "count"
    ] == 4


def test_main_without_a_tpu_fails_and_prints_no_ok():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        capture_output=True, text=True, env=env, cwd=str(ROOT), timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_device_phase_reports_cache_and_native_library(smoke, monkeypatch):
    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        out = smoke.phase_device()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert out["platform"] == "cpu" and out["count"] >= 1
    assert out["compile_cache_dir"].endswith(
        os.path.join(".cache", "jax_cache")
    )
    assert isinstance(out["native_planner_loaded"], bool)


def test_ghz_phase_passes_on_cpu(smoke):
    out = smoke.phase_ghz("jax", qubits=12, depth=6)
    assert out["ghz_abs_err"] <= 1e-5
    assert out["statevector_rel_err_vs_numpy"] <= 1e-5


@pytest.mark.parametrize("split_complex", [False, True])
def test_sliced_amplitude_phase_passes_on_cpu(smoke, split_complex):
    """``split_complex=True`` is the layout the chip runs (the CPU
    default is plain complex64)."""
    from tnc_tpu.ops.backends import JaxBackend

    out = smoke.phase_sliced_amplitude(
        _circuit(), "0" * 12, JaxBackend(split_complex=split_complex),
        "cpu", target_log2=5,
    )
    assert out["num_slices"] > 1
    assert out["slices_run"] == out["num_slices"]
    assert out["projected_all_slices_s"] >= 0
    assert out["sample_rel_err"] <= 1e-5
    assert out["kernel_modes"]["residual_chains"] == 0
    assert not any("fallback" in k for k in out["counters"])


def test_sliced_amplitude_phase_runs_a_stated_prefix(smoke):
    from tnc_tpu.ops.backends import JaxBackend

    out = smoke.phase_sliced_amplitude(
        _circuit(), "0" * 12, JaxBackend(split_complex=True), "cpu",
        target_log2=4, max_slices=8,
    )
    assert out["num_slices"] > 8 and out["slices_run"] == 8
    assert out["sampled_slices"][1] <= 8
    assert out["sample_rel_err"] <= 1e-5


def test_sliced_amplitude_phase_fails_on_the_wrong_platform(smoke):
    from tnc_tpu.ops.backends import JaxBackend

    with pytest.raises(smoke.SmokeFailure, match="expected tpu"):
        smoke.phase_sliced_amplitude(
            _circuit(), "0" * 12, JaxBackend(), "tpu", target_log2=5
        )


@pytest.mark.parametrize("split_complex", [False, True])
def test_serve_phase_passes_on_cpu(smoke, split_complex):
    from tnc_tpu.ops.backends import JaxBackend

    bits = smoke.seeded_bitstrings(3, 12)
    assert len(set(bits)) == 3
    out = smoke.phase_serve(
        _circuit(), bits, JaxBackend(split_complex=split_complex),
        target_log2=5,
    )
    assert out["sliced"] and out["num_slices"] > 1
    assert len(out["amplitudes"]) == 3
    assert max(out["rel_err_vs_direct"]) <= 1e-5
    assert out["stats"]["counts"]["completed"] == 3


def test_four_chip_phases_pass_on_the_virtual_mesh(smoke):
    out = smoke.phase_slice_spmd(
        _circuit(), "0" * 12, 4, "cpu", target_log2=5
    )
    assert out["slices_per_device"] * 4 == out["num_slices"]
    assert out["rel_err_vs_one_chip"] <= 1e-5
    assert len(out["peak_bytes_in_use_after_spmd"]) >= 4
    fan = smoke.phase_partitioned(_circuit(4), 12, 4, sliced_target_log2=4)
    assert fan["partitions"] == 4
    assert fan["global_slices"] > 1 and fan["one_chip_slices"] > 1
    assert fan["plain_rel_err_vs_one_chip"] <= 1e-5
    assert fan["sliced_rel_err_vs_one_chip"] <= 1e-5


def test_check_is_fatal_on_mismatch(smoke):
    assert smoke.check(1e-7, 1e-5, "x") == 1e-7
    with pytest.raises(smoke.SmokeFailure):
        smoke.check(1e-3, 1e-5, "x")
    with pytest.raises(smoke.SmokeFailure):
        smoke.check(float("nan"), 1e-5, "x")
    assert smoke.rel_err([1.0, 2.0], [1.0, 2.0]) == 0.0
    assert smoke.rel_err([1.0], [1.0, 2.0]) == float("inf")
