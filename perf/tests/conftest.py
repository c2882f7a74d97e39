"""``python -m pytest perf/tests -q`` — the benchmark's own tests, on the
CPU (``tests/`` is the program's tier-1 suite and is untouched)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# four virtual devices for the rehearsal of the four-chip traffic kind
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    ).strip()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
