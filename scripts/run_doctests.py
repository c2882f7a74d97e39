#!/usr/bin/env python
"""Docs-as-spec runner (the reference compiles every docstring example in
CI — ``cargo test --doc``, ``.github/workflows/test.yml``): executes the
doctest examples across the WHOLE public module tree and enforces a
coverage floor — every public module must carry at least one runnable
example, mirroring the reference's per-function examples
(``tnc/src/tensornetwork/tensor.rs:74-83`` and throughout).

Pins the CPU platform first — examples must not depend on accelerator
hardware. Modules may opt out via ``__doctest_skip__ = True`` at module
level (reserved for hardware-only surfaces; none today).
"""

from __future__ import annotations

import doctest
import importlib
import os
import pkgutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

# Modules that are exempt from the one-example floor (entry points and
# re-export shims whose behavior is pinned by the suite instead):
FLOOR_EXEMPT = {
    "tnc_tpu.benchmark.cli",  # argparse entry point (subprocess-tested)
    "tnc_tpu.benchmark.logging_util",  # process-global logging config
    "tnc_tpu.partitioning.native_binding",  # ctypes loader (env-dependent)
}


def public_modules() -> list[str]:
    import tnc_tpu

    names = ["tnc_tpu"]
    for info in pkgutil.walk_packages(tnc_tpu.__path__, prefix="tnc_tpu."):
        if any(part.startswith("_") for part in info.name.split(".")):
            continue
        names.append(info.name)
    return sorted(names)


def main() -> int:
    failures = attempts = 0
    missing: list[str] = []
    for name in public_modules():
        try:
            mod = importlib.import_module(name)
        except Exception as e:  # noqa: BLE001 — import failure IS a failure
            print(f"{name}: IMPORT FAILED ({type(e).__name__}: {e})")
            failures += 1
            continue
        result = doctest.testmod(mod, verbose=False)
        failures += result.failed
        attempts += result.attempted
        is_shim = getattr(mod, "__file__", "").endswith("__init__.py")
        if (
            result.attempted == 0
            and name not in FLOOR_EXEMPT
            and not is_shim
            and not getattr(mod, "__doctest_skip__", False)
        ):
            missing.append(name)
        status = "ok" if result.failed == 0 else f"{result.failed} FAILED"
        print(f"{name}: {result.attempted} examples, {status}")
    print(f"doctests: {attempts} examples, {failures} failures")
    if missing:
        print(
            f"FLOOR VIOLATION: {len(missing)} public modules without a "
            f"single runnable example:"
        )
        for name in missing:
            print(f"  - {name}")
        return 1
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
