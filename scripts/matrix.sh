#!/usr/bin/env bash
# Configuration-matrix tier — the Python analogue of the
# reference's `cargo hack --feature-powerset` CI
# (.github/workflows/check.yml): re-run the knob-sensitive test subset
# under each configuration axis. The default configuration's FULL suite
# runs in check.sh; these cells pin that the feature toggles don't only
# work in the default combination.
#
#   cell 1  TNC_TPU_NO_NATIVE=1        pure-Python partitioner/replayer
#   cell 2  TNC_TPU_COMPLEX_MULT=gauss  3-dot split-complex kernel
#   cell 3  TNC_TPU_COMPLEX_MULT=fused  Pallas fused kernel (interpret)
#   cell 4  1 virtual device            no mesh available: single-chip paths
#   cell 5  8 virtual devices + naive   (the default combination re-pinned
#                                        on the knob-sensitive subset)
set -euo pipefail
cd "$(dirname "$0")/.."

# Per-axis test subsets (kept lean: the matrix multiplies runtimes).
NATIVE_TESTS="tests/test_km1_partitioning.py tests/test_native_partitioner.py \
  tests/test_slicereplay_native.py"
CMULT_TESTS="tests/test_kahan.py tests/test_pallas_complex.py \
  tests/test_staged_prep.py"
# Single-chip subset for the 1-device cell (no Mesh construction).
SINGLE_TESTS="tests/test_contraction.py tests/test_kahan.py \
  tests/test_budget.py tests/test_treecut.py"

run_cell() {
  name=$1; shift
  echo "== matrix cell: $name =="
  env "$@" python -m pytest -q -p no:cacheprovider $TESTS
}

TESTS=$NATIVE_TESTS run_cell "no-native"    TNC_TPU_NO_NATIVE=1
TESTS=$CMULT_TESTS run_cell "cmult-gauss"  TNC_TPU_COMPLEX_MULT=gauss
TESTS=$CMULT_TESTS run_cell "cmult-fused"  TNC_TPU_COMPLEX_MULT=fused
TESTS=$SINGLE_TESTS run_cell "1-device" \
  XLA_FLAGS=--xla_force_host_platform_device_count=1
TESTS=$CMULT_TESTS run_cell "8-device-naive" TNC_TPU_COMPLEX_MULT=naive \
  XLA_FLAGS=--xla_force_host_platform_device_count=8

echo "MATRIX PASSED (5 cells)"
